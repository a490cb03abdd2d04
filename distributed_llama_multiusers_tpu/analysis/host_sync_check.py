"""host-sync: device->host transfers in the decode path must be explicit.

The serving invariant: one small host transfer per decode step (a token
id a lane, never a row of logits). A stray ``np.asarray(logits)`` / ``.item()``
in the engine step functions or the scheduler loop silently serializes the
pipeline on a full [n_lanes, vocab] f32 row every token — the classic
silent throughput killer on an accelerator behind a high-latency link.

Scope: the decode-path files (``runtime/engine.py``,
``runtime/scheduler.py``, ``runtime/spec.py``) plus the whole
``telemetry/`` package — the scheduler hands telemetry hooks values from
inside the serving loop, so a stray ``np.asarray``/``.item()`` there
would serialize the decode path from one layer out; telemetry is pure
stdlib by contract and should never need a waiver. Three sub-rules:

1. **transfer calls** — every ``np.asarray`` / ``np.array`` /
   ``jax.device_get`` call, and every ``.item()`` / ``.tolist()`` /
   ``.block_until_ready()`` / ``.all_logits()`` / ``.lane_logits()``
   method call, needs a waiver. The intentional single-transfer sites
   (the packed token readback per step, the host-exact logits row) carry
   waivers stating exactly what is transferred and why.
2. **casts** (``runtime/engine.py`` only) — ``int()`` / ``float()`` /
   ``bool()`` over a name that is not host-annotated forces a device
   sync. Host-side numpy results use the ``*_np`` naming convention and
   are exempt; everything else needs a waiver.
3. **implicit bool** — ``if x:`` / ``while x:`` on a value returned by a
   compiled step function (names assigned from ``*_fn`` / ``*_exec``
   calls) blocks on the device to evaluate truthiness.
"""

from __future__ import annotations

import ast
import re

from .core import (
    Checker,
    Finding,
    Project,
    SourceFile,
    last_component,
    root_name,
    walk_with_ancestors,
)

SCOPE = (
    "runtime/engine.py", "runtime/scheduler.py", "runtime/spec.py",
    # the paged KV pool's bookkeeping runs inside the admission path
    # (runtime/scheduler._start_request -> engine.paged_admit); host
    # dicts/lists by contract, never a device value
    "runtime/kvpool.py",
    # the telemetry package rides the serving loop (scheduler hooks);
    # registered file-by-file because scope matching is suffix-based
    "telemetry/__init__.py", "telemetry/hub.py", "telemetry/spans.py",
    "telemetry/metrics.py", "telemetry/trace.py", "telemetry/logs.py",
    # the fleet trace context rides every hop the router makes AND the
    # replica admission path (journal admit records) — pure stdlib by
    # the same contract as the rest of telemetry/
    "telemetry/tracectx.py",
    # failure containment rides the serving loop too: the breaker is fed
    # from every engine step, the watchdog brackets every blocking call,
    # and the fault hooks sit inside the dispatch paths — none of them
    # may ever touch a device value
    "serving/breaker.py", "serving/watchdog.py", "utils/faults.py",
    # crash durability rides it the same way: admit/finish records are
    # enqueued from the serving loop, relay pushes run inside _stream,
    # and recovery re-admits through submit() — all host-side by
    # contract, never holding a device value
    "serving/journal.py", "serving/recovery.py", "serving/resume.py",
    # the fleet front-end is pure stdlib BY DESIGN (the router holds no
    # model, no tokenizer, no device): a transfer spelling appearing in
    # any of these would mean device state leaked a layer up
    "fleet/__init__.py", "fleet/balancer.py", "fleet/router.py",
    "fleet/migrate.py",
    # grammar-constrained decoding rides the admission + dispatch paths
    # (scheduler _start_request -> engine.grammar_attach; per-dispatch
    # mask-state vectors): the compiler and slab are pure-host numpy BY
    # CONTRACT — a device transfer spelling here would serialize every
    # constrained dispatch on the automaton tables
    "grammar/__init__.py", "grammar/automaton.py", "grammar/slab.py",
    # disaggregated prefill is pure stdlib BY DESIGN like fleet/: page
    # payloads cross replicas as OPAQUE bytes behind the engine's
    # export/import hooks — a transfer spelling here would mean device
    # state leaked into the hand-off orchestration layer
    "disagg/__init__.py", "disagg/kvtransfer.py", "disagg/prefill.py",
)
CAST_SCOPE = ("runtime/engine.py",)

SYNC_METHODS = {"item", "tolist", "block_until_ready", "all_logits",
                "lane_logits", "device_get"}
SYNC_FUNCS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array",
              "jax.device_get"}
# declared for what it is: ``jax.Array.is_ready()`` polls the array's state,
# neither blocks nor transfers, and is legal wherever a sync is not (the
# scheduler's dry-dispatch witness, engine.pipeline_ready). Listed so that
# nobody adds it to SYNC_METHODS by analogy with block_until_ready
POLL_METHODS = {"is_ready"}
CASTS = {"int", "float", "bool"}
# compiled-step callables by convention: jit handles stored as *_fn/*_exec
DEVICE_FN_RE = re.compile(r"(_fn|_exec)$")
DEVICE_FN_EXPR_RE = re.compile(r"\b\w*(_fn|_exec)\b")
# host-side numpy results by convention (toks_np, logits_np, out_np, ...)
HOST_NAME_RE = re.compile(r"(_np|_host)$")


class HostSyncChecker(Checker):
    name = "host-sync"
    description = (
        "device->host syncs (np.asarray/.item()/casts/implicit bool) in "
        "the decode path must carry a waiver naming the transfer"
    )

    def check(self, sf: SourceFile, project: Project):
        if not sf.endswith(*SCOPE):
            return
        cast_scoped = sf.endswith(*CAST_SCOPE)
        for node, ancestors in walk_with_ancestors(sf.tree):
            if isinstance(node, ast.Call):
                yield from self._check_call(sf, node, cast_scoped)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_implicit_bool(sf, node)

    # -- rule 1 + 2: transfer calls and casts -------------------------------

    def _check_call(self, sf: SourceFile, node: ast.Call, cast_scoped: bool):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in SYNC_METHODS:
            yield Finding(
                self.name, sf.display, node.lineno,
                f"device->host sync '{ast.unparse(func)}(...)' in the decode "
                "path needs '# dlint: ok[host-sync] <what is transferred and "
                "why>'",
            )
            return
        if ast.unparse(func) in SYNC_FUNCS:
            yield Finding(
                self.name, sf.display, node.lineno,
                f"device->host sync '{ast.unparse(func)}(...)' in the decode "
                "path needs '# dlint: ok[host-sync] <what is transferred and "
                "why>'",
            )
            return
        if (
            cast_scoped
            and isinstance(func, ast.Name)
            and func.id in CASTS
            and len(node.args) == 1
            and not node.keywords
            and isinstance(node.args[0], (ast.Name, ast.Attribute, ast.Subscript))
        ):
            root = root_name(node.args[0])
            if root is not None and not HOST_NAME_RE.search(root):
                yield Finding(
                    self.name, sf.display, node.lineno,
                    f"cast '{func.id}({ast.unparse(node.args[0])})' syncs a "
                    "device value to host; read from a *_np host array or "
                    "waive the intentional transfer",
                )

    # -- rule 3: implicit bool on compiled-step outputs ---------------------

    def _check_implicit_bool(self, sf: SourceFile, func_node):
        device_fns: set[str] = set()
        tainted: set[str] = set()
        for stmt in ast.walk(func_node):
            if not isinstance(stmt, ast.Assign):
                continue
            rhs = stmt.value
            if isinstance(rhs, ast.Call):
                callee = rhs.func
                last = last_component(callee)
                is_device = (
                    last is not None and DEVICE_FN_RE.search(last) is not None
                ) or (isinstance(callee, ast.Name) and callee.id in device_fns)
                if is_device:
                    for tgt in stmt.targets:
                        tainted.update(self._target_names(tgt))
            elif DEVICE_FN_EXPR_RE.search(ast.unparse(rhs)):
                # e.g. fn = self._decode_exec if ... else self._decode_fn
                for tgt in stmt.targets:
                    device_fns.update(self._target_names(tgt))
        if not tainted:
            return
        for node in ast.walk(func_node):
            if not isinstance(node, (ast.If, ast.While, ast.Assert)):
                continue
            test = node.test
            for name in self._bool_names(test):
                if name in tainted:
                    yield Finding(
                        self.name, sf.display, node.lineno,
                        f"implicit bool of device value '{name}' blocks on "
                        "the device; compare against a host copy or waive",
                    )

    @staticmethod
    def _target_names(tgt: ast.AST) -> list[str]:
        if isinstance(tgt, ast.Name):
            return [tgt.id]
        if isinstance(tgt, (ast.Tuple, ast.List)):
            return [e.id for e in tgt.elts if isinstance(e, ast.Name)]
        return []

    @staticmethod
    def _bool_names(test: ast.AST) -> list[str]:
        if isinstance(test, ast.Name):
            return [test.id]
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return HostSyncChecker._bool_names(test.operand)
        if isinstance(test, ast.BoolOp):
            out: list[str] = []
            for v in test.values:
                out.extend(HostSyncChecker._bool_names(v))
            return out
        return []
