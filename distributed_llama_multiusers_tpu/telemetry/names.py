"""The fixed names a trace of this program is read by, declared once.

Three users: the program (``models/llama.py`` and ``runtime/engine.py`` wrap
the parts of every step family in ``jax.named_scope(SCOPE_*)``;
``runtime/scheduler.py`` opens the ``LOOP_*`` spans), the readers
(``benchmarks/harness/progtrace.py``, ``stepclass.py``) and the tests. Strings
only: this module imports nothing but ``re``, so the benchmark's readers can
import it without pulling in jax.

**Device scopes.** A scope is HLO metadata (the ``op_name`` of every
instruction traced inside it): it costs the device nothing, and JAX's
compile-cache key ignores it. Scopes nest; an operation belongs to the
DEEPEST ``dl.*`` component of its ``op_name``. ``dl.layers`` wraps the
``lax.scan`` over the layers and is the only scope with children, so its
self time (what runs inside the scan under none of the five layer scopes)
plus the operations that carry no scope at all is what XLA adds around the
model's own arithmetic: whole-cache carry copies, per-layer slices and
updates of the stacked cache and of the stacked weight planes.

**Step classes and halves.** A second kind of name, which ``_SCOPE_RE`` does
not match (``scope_path`` and ``scope_of`` return the same with and without
it): every jitted step program wraps its whole body in ONE
``dlstep.<family>[.b<width>]`` (``step_class``; the width is the static shape
it was compiled for: its chunk's bucket, a multi-step program's horizon), and
inside it the admitted chunk's operations sit under ``dlhalf.prefill`` and the
decode batch's under ``dlhalf.decode``. What joins the two (the closing ``dl.carry`` block) sits
under neither. A device trace then says which program an execution was and
whose operations it ran, which its module name (``jit__decode_prefill(<hash>)``,
the same for every bucket) does not.

**Host spans.** ``Telemetry.span(name, track)`` records a ring slice named
``name`` and holds a profiler annotation named ``ANNOTATION_PREFIX + name``
open while it runs, so the host plane of a ``jax.profiler`` trace carries the
batching loop on the device's clock.
"""

from __future__ import annotations

import re

SCOPE_PREFIX = "dl."

SCOPE_EMBED = "dl.embed"          # token embedding gather
SCOPE_LAYERS = "dl.layers"        # the lax.scan over layers (has children)
SCOPE_QKV = "dl.qkv"              # norm, q/k/v matmuls, biases, RoPE
SCOPE_KV_WRITE = "dl.kv_write"    # fresh K/V rows scattered into the cache
SCOPE_ATTENTION = "dl.attention"  # cache plane convert / paged gather, scores, softmax, values
SCOPE_ATTN_OUT = "dl.attn_out"    # wo matmul (+ its TP sync) and the residual add
SCOPE_FFN = "dl.ffn"              # norm, dense gated FFN or MoE, residual add
SCOPE_HEAD = "dl.head"            # final norm, wcls
SCOPE_SAMPLER = "dl.sampler"      # grammar mask, argmax, full-vocab nucleus sample
SCOPE_CARRY = "dl.carry"          # token/position/grammar carry, admitted-lane splice, packs

# the latent-attention block with a routed FFN (models/deepseek.py) nests
# these under the scopes above, in every step family; an operation belongs to
# the deepest, so the parents keep what is left (dl.qkv: the norm and the
# query's matmul and rotation; dl.ffn: the norm and the residual add)
SCOPE_KV_LATENT = "dl.kv_latent"  # under dl.qkv: down projection, its norm, the key's rotation
SCOPE_ROUTER = "dl.router"        # under dl.ffn: router logits, scores, top-k, weights
SCOPE_EXPERTS = "dl.experts"      # under dl.ffn: sort by expert, grouped kernel, combine
SCOPE_SHARED_EXPERT = "dl.shared_expert"  # under dl.ffn: the always-on gated FFN
LATENT_BLOCK_SCOPES = (SCOPE_KV_LATENT, SCOPE_ROUTER, SCOPE_EXPERTS, SCOPE_SHARED_EXPERT)

# learned sparse attention in that block (config.index_topk): two scopes
# beside dl.attention, under its parent, so dl.attention keeps what attends
# the chosen rows and nothing else
SCOPE_INDEXER = "dl.indexer"  # the indexer's projections, norm, rotation, its key's append, the score pass
SCOPE_SPARSE_SELECT = "dl.sparse_select"  # the top-k of the scores, and the gather of the chosen rows or their mask
SPARSE_ATTENTION_SCOPES = (SCOPE_INDEXER, SCOPE_SPARSE_SELECT)

# a block whose layers differ in their mixer (models/hybrid.py): a conv layer
# takes dl.conv in the place of the four attention scopes; the FFN scopes are
# the routed block's above
SCOPE_CONV = "dl.conv"              # the short-conv mixer: norm, in-projection, gates, taps, out-projection
SCOPE_CONV_STATE = "dl.conv_state"  # under dl.conv: the lane state's read and its commit
CONV_MIXER_SCOPES = (SCOPE_CONV, SCOPE_CONV_STATE)
# a selective state-space layer in such a block (ops/ssm_scan.py) takes dl.ssm
# in the place of the four attention scopes
SCOPE_SSM = "dl.ssm"            # the state-space mixer: norm, projections, conv, gates, out-projection
SCOPE_SSM_SCAN = "dl.ssm_scan"  # under dl.ssm: the running sum's read, the recurrence and its commit
SSM_MIXER_SCOPES = (SCOPE_SSM, SCOPE_SSM_SCAN)

# a window layer in such a block (LayerKind.WINDOW) keeps the four attention
# scopes, and nests its read of the ring under dl.attention, so that a trace
# tells the two kinds of attention layer apart
SCOPE_WINDOW_ATTENTION = "dl.window_attention"  # under dl.attention: a window layer's read of its ring

# a linear-attention layer in such a block (LayerKind.LINEAR;
# ops/linear_attention.py) takes dl.linear_attention in the place of the four
# attention scopes
SCOPE_LINEAR_ATTENTION = "dl.linear_attention"  # the mixer: norm, projections, head norms, rotation, gate, out-projection
SCOPE_LINEAR_STATE = "dl.linear_state"  # under dl.linear_attention: the matrix state's read, the recurrence and its commit
LINEAR_MIXER_SCOPES = (SCOPE_LINEAR_ATTENTION, SCOPE_LINEAR_STATE)
# a gated delta-rule layer in such a block (LayerKind.DELTA; ops/delta_rule.py)
# takes dl.delta in the place of the four attention scopes
SCOPE_DELTA = "dl.delta"  # the mixer: norm, projections, L2 norms, the decay's and b's gates, output norm and gate, out-projection
SCOPE_DELTA_CONV = "dl.delta_conv"  # under dl.delta: the three short convs and their windows' read and commit
SCOPE_DELTA_STATE = "dl.delta_state"  # under dl.delta: the matrix state's read, the recurrence or chunk form, its commit
DELTA_MIXER_SCOPES = (SCOPE_DELTA, SCOPE_DELTA_CONV, SCOPE_DELTA_STATE)
# a block-sparse layer (LayerKind.SPARSE) keeps the four attention scopes and
# adds two beside dl.attention: dl.block_scores, and dl.sparse_select (above)
# for the top-k of the block scores and the list or mask made of it
SCOPE_BLOCK_SCORES = "dl.block_scores"  # the compressed keys' append, the score pass over them, the pooling to blocks

# scopes inside the layer scan, in program order
LAYER_SCOPES = (SCOPE_QKV, SCOPE_KV_WRITE, SCOPE_ATTENTION, SCOPE_ATTN_OUT, SCOPE_FFN)
# every scope whose time is the model's own arithmetic (no children)
LEAF_SCOPES = (SCOPE_EMBED, *LAYER_SCOPES, SCOPE_HEAD, SCOPE_SAMPLER, SCOPE_CARRY)
ALL_SCOPES = (SCOPE_EMBED, SCOPE_LAYERS, *LEAF_SCOPES[1:])

STEP_PREFIX = "dlstep."
# jitted step program (the name a trace's ``XLA Modules`` line gives its
# executions, ``jit_<name>(<hash>)``) -> family of its class
STEP_PROGRAMS = {
    "_decode_pl": "decode",                 # the pipelined decode step
    "_decode_prefill": "fused",             # + a prompt chunk; by the chunk's bucket
    "_prefill": "prefill",                  # the synchronous chunk; by its bucket
    "_decode": "decode_sync",               # the synchronous step that returns its logits
    "_decode_nologits": "decode_sync_nologits",
    "_decode_spec": "spec",                 # the synchronous verify step
    "_decode_spec_pl": "spec_pl",           # the verify step inside the chain
    "_decode_spec_prefill": "spec_fused",   # + a prompt chunk; by the chunk's bucket
    "_decode_multi": "decode_multi",        # h chained steps; by h
}

HALF_PREFIX = "dlhalf."
HALF_PREFILL = "dlhalf.prefill"  # _prefill_half: the admitted chunk, every row of its bucket
HALF_DECODE = "dlhalf.decode"    # the decode batch: positions, _decode_core or the verify core
HALVES = (HALF_PREFILL, HALF_DECODE)

ANNOTATION_PREFIX = "dl."

LOOP_TRACK = "loop"
LOOP_ADMIT = "loop.admit"        # queue sweep, cancel checks, _claim_admissions (+ tokenization)
LOOP_DISPATCH = "loop.dispatch"  # the call to _pipeline_dispatch
LOOP_WAIT = "loop.wait"          # engine.pipeline_consume() alone: the lagged readback
LOOP_STREAM = "loop.stream"      # the rest of _pipeline_consume: commit the fed token, stream the produced one (detokenize, on_delta)
LOOP_SPANS = (LOOP_ADMIT, LOOP_DISPATCH, LOOP_WAIT, LOOP_STREAM)
# the loop's own work: what the host does while the device may run dry
LOOP_HOST_SPANS = (LOOP_ADMIT, LOOP_DISPATCH, LOOP_STREAM)


_SCOPE_RE = re.compile(r"(?<![\w.])dl\.[a-z_]+")


def scope_path(op_name: str) -> list[str]:
    """Every ``dl.*`` component of an HLO ``op_name``, outermost first
    (``jit(_decode_pl)/dl.layers/while/body/closed_call/dl.attention/dot_general``
    -> ``["dl.layers", "dl.attention"]``). A transform wraps the component it
    follows (``vmap(dl.sampler)``), hence a search and not a split."""
    return _SCOPE_RE.findall(op_name)


def scope_of(op_name: str) -> str | None:
    """The scope an operation belongs to: the deepest ``dl.*`` component of
    its ``op_name``; None for an operation under no scope."""
    found = scope_path(op_name)
    return found[-1] if found else None


_STEP_RE = re.compile(r"(?<![\w.])dlstep\.[a-z_]+(?:\.b\d+)?")
_HALF_RE = re.compile(r"(?<![\w.])dlhalf\.[a-z_]+")


def step_class(family: str, width: int | None = None) -> str:
    """``dlstep.<family>`` or, for a program compiled once a static width
    (a prefill bucket, a multi-step horizon), ``dlstep.<family>.b<width>``."""
    return f"{STEP_PREFIX}{family}" if width is None else f"{STEP_PREFIX}{family}.b{int(width)}"


def pipelined_step_class(spec: bool, bucket: int | None = None) -> str:
    """The class of the program a step of the pipelined loop dispatched, from
    what the host holds: whether it shipped drafts to verify (``spec``) and
    the prefill bucket its prompt chunk rode (None: no chunk). The SAME
    string the device program wraps its body in, so a host record and a
    device execution of one step carry one name."""
    if bucket is None:
        return step_class("spec_pl" if spec else "decode")
    return step_class("spec_fused" if spec else "fused", bucket)


def step_class_of(op_name: str) -> str | None:
    """The ``dlstep.*`` class in an ``op_name`` (the outermost, should a step
    program ever be traced inside another); None where there is none."""
    m = _STEP_RE.search(op_name)
    return m.group(0) if m else None


def half_of(op_name: str) -> str | None:
    """``dlhalf.prefill`` or ``dlhalf.decode``; None for an operation of the
    join, or of a program from before the halves."""
    m = _HALF_RE.search(op_name)
    return m.group(0) if m else None
