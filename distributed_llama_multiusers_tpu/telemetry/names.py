"""The fixed names a trace of this program is read by, declared once.

Three users: the program (``models/llama.py`` and ``runtime/engine.py`` wrap
the parts of every step family in ``jax.named_scope(SCOPE_*)``;
``runtime/scheduler.py`` opens the ``LOOP_*`` spans), the readers
(``benchmarks/harness/progtrace.py``) and the tests. Strings only: this
module imports nothing but ``re``, so the benchmark's readers can import it without
pulling in jax.

**Device scopes.** A scope is HLO metadata (the ``op_name`` of every
instruction traced inside it): it costs the device nothing, and JAX's
compile-cache key ignores it. Scopes nest; an operation belongs to the
DEEPEST ``dl.*`` component of its ``op_name``. ``dl.layers`` wraps the
``lax.scan`` over the layers and is the only scope with children, so its
self time (what runs inside the scan under none of the five layer scopes)
plus the operations that carry no scope at all is what XLA adds around the
model's own arithmetic: whole-cache carry copies, per-layer slices and
updates of the stacked cache and of the stacked weight planes.

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

# a block whose layers differ in their mixer (models/hybrid.py): a conv layer
# takes dl.conv in the place of the four attention scopes; the FFN scopes are
# the routed block's above
SCOPE_CONV = "dl.conv"              # the short-conv mixer: norm, in-projection, gates, taps, out-projection
SCOPE_CONV_STATE = "dl.conv_state"  # under dl.conv: the lane state's read and its commit
CONV_MIXER_SCOPES = (SCOPE_CONV, SCOPE_CONV_STATE)

# scopes inside the layer scan, in program order
LAYER_SCOPES = (SCOPE_QKV, SCOPE_KV_WRITE, SCOPE_ATTENTION, SCOPE_ATTN_OUT, SCOPE_FFN)
# every scope whose time is the model's own arithmetic (no children)
LEAF_SCOPES = (SCOPE_EMBED, *LAYER_SCOPES, SCOPE_HEAD, SCOPE_SAMPLER, SCOPE_CARRY)
ALL_SCOPES = (SCOPE_EMBED, SCOPE_LAYERS, *LEAF_SCOPES[1:])

ANNOTATION_PREFIX = "dl."

LOOP_TRACK = "loop"
LOOP_ADMIT = "loop.admit"        # queue sweep, cancel checks, _claim_admissions (+ tokenization)
LOOP_DISPATCH = "loop.dispatch"  # the call to _pipeline_dispatch
LOOP_WAIT = "loop.wait"          # engine.pipeline_consume() alone: the lagged readback
LOOP_STREAM = "loop.stream"      # the rest of _pipeline_consume: _consume, detokenize, on_delta
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
