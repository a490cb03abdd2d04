"""jit-stability / donation-discipline / warmup-coverage: compile
stability as a machine-checked invariant.

The serving loop's contract is *one compiled program per (family,
bucket), compiled only at warmup* — a mid-serving XLA recompile stalls
every lane for seconds exactly when they are hot, and (PR 11's lesson)
the two ways to lose it are silent: a device-pytree leaf rebuilt with a
different sharding/aval recompiles every warmed program on the next
dispatch, and a step family the warmup loop missed compiles on its
first live dispatch. All three checks consume the surface model
``jitmodel.extract_jit_model`` builds (the ``protocol_check`` pattern);
the runtime twin is ``analysis/jitcheck.py`` (``DLLAMA_JITCHECK=1``).

- ``jit-stability`` — inside an engine method (scope:
  ``runtime/engine.py``), storing a bare ``jnp.asarray`` /
  ``jnp.array`` result (or a sharding-less ``jax.device_put``) into
  ``self`` state is a finding: device-pytree leaves must be built by
  the ONE sanctioned sharding-preserving constructor
  (``InferenceEngine._replace_leaf`` — ``make_array_from_callback`` /
  ``device_put`` with the captured ``NamedSharding``), so a leaf
  replacement can never change the compiled programs' input aval.
- ``donation-discipline`` — every ``donate_argnums`` call site must
  rebind the donated operand from the call's own results
  (``..., self.cache = self._fn(self.params, self.cache, ...)``);
  reading a donated value after the call (use-after-donate) or storing
  it into other host-side state before the call (the alias outlives the
  donation) is a finding.
- ``warmup-coverage`` — the set of dispatchable step families (every
  ``self.*_fn``-style jit binding: decode/pipelined/fused/spec
  families, ``_copy_page_fn``, ``_copy_lane_fn``, ``_sample_one``, the
  ``decode_multi`` factory) is cross-checked against what
  ``warmup_engine`` actually warms: a family reachable from a dispatch
  method but absent from warmup fails lint (the PR 11 COW-compile
  class), as does a bucketed family warmed outside the
  ``prefill_buckets`` loop, and a family no dispatcher can reach (dead
  compiled surface — the ``device_topk`` class).
"""

from __future__ import annotations

import ast

from .core import Checker, Finding, Project, SourceFile
from .jitmodel import extract_jit_model
from .lockgraph import walk_excluding_nested_defs

ENGINE_SCOPE = ("runtime/engine.py",)
# donation sites exist beyond the engine (the trainer's fused step); the
# jit surface the issue scopes is engine + model + ops + grammar slab
DONATION_SCOPE = (
    "runtime/engine.py", "models/llama.py", "grammar/slab.py",
    "training/trainer.py",
)
DONATION_DIRS = ("/ops/",)

# THE sanctioned leaf constructor: the one place a host mirror may
# become a device leaf. dlint whitelists exactly this name; everything
# else (the table leaf, the grammar-slab upload) must route through it.
SANCTIONED_LEAF_FNS = ("_replace_leaf",)

_BARE_LEAF_CALLS = {
    "jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.numpy.array",
}


def _spelled(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover
        return ""


def _self_target_attr(node: ast.AST) -> str | None:
    """``self.x`` / ``self.x[...]`` assignment target -> ``x``."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


class JitStabilityChecker(Checker):
    name = "jit-stability"
    description = (
        "device-pytree leaves stored into engine state must come from "
        "the sanctioned sharding-preserving constructor (_replace_leaf), "
        "never a bare jnp.asarray/jnp.array — a changed leaf aval forces "
        "an XLA recompile of every warmed program mid-serving"
    )

    def check(self, sf: SourceFile, project: Project):
        if not sf.endswith(*ENGINE_SCOPE):
            return
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for fn in node.body:
                if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if fn.name == "__init__" or fn.name in SANCTIONED_LEAF_FNS:
                    # __init__ builds the initial pytree (the avals every
                    # program is compiled against); the sanctioned
                    # constructor is the whitelist itself
                    continue
                yield from self._check_method(sf, fn)

    def _check_method(self, sf: SourceFile, fn):
        for node in walk_excluding_nested_defs(fn):
            if not isinstance(node, ast.Assign):
                continue
            stored = [a for a in
                      (_self_target_attr(t) for t in node.targets)
                      if a is not None]
            if not stored:
                continue
            for sub in ast.walk(node.value):
                if not isinstance(sub, ast.Call):
                    continue
                spelled = _spelled(sub.func)
                if spelled in _BARE_LEAF_CALLS:
                    yield Finding(
                        self.name, sf.display, sub.lineno,
                        f"engine state 'self.{stored[0]}' rebuilt with "
                        f"bare {spelled}(...) — on a mesh the new leaf "
                        "drops the captured NamedSharding, the compiled "
                        "programs' input aval changes, and every warmed "
                        "family recompiles on the next dispatch (the PR 11 "
                        "per-admission-recompile class); build the leaf "
                        "with the sanctioned _replace_leaf constructor",
                    )
                elif spelled == "jax.device_put" and len(sub.args) < 2 \
                        and not any(kw.arg in ("device", "sharding")
                                    for kw in sub.keywords):
                    yield Finding(
                        self.name, sf.display, sub.lineno,
                        f"engine state 'self.{stored[0]}' rebuilt with "
                        "jax.device_put(...) without an explicit sharding "
                        "— the default placement is single-device, which "
                        "changes the leaf aval on a mesh; pass the "
                        "captured NamedSharding (or use _replace_leaf)",
                    )


class DonationDisciplineChecker(Checker):
    name = "donation-discipline"
    description = (
        "donate_argnums call sites rebind the donated operand from the "
        "call's results; reading a donated value after the call, or "
        "aliasing it into host state before it, touches a freed buffer"
    )

    def _in_scope(self, sf: SourceFile) -> bool:
        if sf.endswith(*DONATION_SCOPE):
            return True
        p = sf.path.as_posix()
        return any(d in p for d in DONATION_DIRS) and p.endswith(".py")

    def check(self, sf: SourceFile, project: Project):
        if not self._in_scope(sf):
            return
        model = extract_jit_model(sf.tree, sf.display)
        if not model.families:
            return
        for d in model.dispatchers.values():
            for use in d.donate_calls:
                if use.escape_line is not None:
                    # escapes even when the call rebinds: the pre-call
                    # alias still points at the freed buffer
                    yield Finding(
                        self.name, sf.display, use.escape_line,
                        f"donated pytree escapes into host-side state: "
                        f"'{use.spelling}' is stored here and then "
                        f"donated to {use.family} at line {use.line} — "
                        "the stored alias refers to a freed device "
                        "buffer after the call",
                    )
                if use.rebound:
                    continue
                if use.later_read_line is not None:
                    yield Finding(
                        self.name, sf.display, use.later_read_line,
                        f"use-after-donate: '{use.spelling}' was donated "
                        f"to {use.family} at line {use.line} "
                        "(donate_argnums) and is read again here — the "
                        "buffer was freed into the call's workspace; "
                        "rebind it from the call's results "
                        f"(`..., {use.spelling} = ...{use.family}(...)`)",
                    )


class WarmupCoverageChecker(Checker):
    name = "warmup-coverage"
    description = (
        "every dispatchable compiled step family is warmed by "
        "warmup_engine (bucketed families per prefill bucket) — a "
        "family missing from warmup compiles mid-serving on its first "
        "live dispatch (the PR 11 COW-compile class)"
    )

    def check(self, sf: SourceFile, project: Project):
        if not sf.endswith(*ENGINE_SCOPE):
            return
        model = extract_jit_model(sf.tree, sf.display)
        if not model.families:
            return
        if not model.has_warmup:
            yield Finding(
                self.name, sf.display, 1,
                f"{len(model.families)} compiled step families but no "
                "warmup_engine function — every family compiles "
                "mid-serving on its first dispatch",
            )
            return

        # several attrs can bind one site (the decode_multi factory and
        # its per-horizon dict): group by site so one warmed alias
        # covers the family
        groups: dict[int, list[str]] = {}
        for attr, site in model.families.items():
            groups.setdefault(id(site), []).append(attr)

        warmed_fams = model.warmed_families()
        for _, attrs in sorted(groups.items(),
                               key=lambda kv: model.family_lines[kv[1][0]]):
            attrs.sort(key=lambda a: model.family_lines[a])
            head = attrs[0]
            line = model.family_lines[head]
            dispatchers = sorted(
                d.name for d in model.dispatchers.values()
                if any(a in d.families for a in attrs)
            )
            if not dispatchers:
                yield Finding(
                    self.name, sf.display, line,
                    f"compiled family '{head}' is dispatched by no engine "
                    "method — dead device-program surface (compile cost "
                    "and warmup time for a program nothing can run); "
                    "delete it or wire a dispatcher",
                )
                continue
            if not any(a in warmed_fams for a in attrs):
                yield Finding(
                    self.name, sf.display, line,
                    f"compiled family '{head}' (dispatched by "
                    f"{', '.join(dispatchers)}) is never warmed by "
                    "warmup_engine — its first live dispatch pays the "
                    "XLA compile mid-serving; warm it (the PR 11 "
                    "COW-compile class)",
                )

        # bucketed dispatchers compile one program per prefill bucket:
        # warming one bucket leaves the others to compile mid-serving
        for method, call in sorted(model.warmed.items()):
            d = model.dispatchers.get(method)
            if d is not None and d.bucketed and d.families \
                    and not call.in_bucket_loop:
                yield Finding(
                    self.name, sf.display, call.line,
                    f"bucketed dispatcher '{method}' is warmed outside "
                    "the `for ... in engine.prefill_buckets` loop — only "
                    "one bucket's program compiles at warmup; the other "
                    "buckets compile on their first live admission",
                )
