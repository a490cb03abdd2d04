"""Inference engine: compiled decode/prefill steps over a lane-based KV cache.

This is the TPU-native replacement for the reference executor + forward loop
(src/nn/nn-executor.cpp:134-187, src/app.cpp:179-231): instead of a
spin-barrier thread pool stepping a flat op list and shipping control packets
to workers, there are two compiled XLA programs —

- ``decode``: one token for every lane at its own position (the whole
  continuous batch advances in a single device step), and
- ``prefill``: a bucketed prompt chunk for ONE lane (dynamic-sliced out of
  the lane axis so other lanes' caches are untouched) — full prompt
  processing, fixing reference defect (a).

Shapes are bucketed (prompt chunks padded up to fixed sizes) so XLA compiles
a handful of programs once, replacing the reference's dynamic ``batchSize``
argument (nn-executor.cpp:171). All per-lane state (positions, sampling,
stream decode) lives with the scheduler; the engine is stateless apart from
the device-resident cache it threads through.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial, wraps

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import jitcheck
from ..grammar.slab import (
    DEFAULT_SLAB_EDGES,
    DEFAULT_SLAB_STATES,
    GrammarSlab,
)
from ..lockcheck import make_lock
from ..models.config import LlamaConfig
from ..models.llama import (
    KVCache,
    LlamaParams,
    PagedKVCache,
    decode_attention_engages,
    init_kv_cache,
    init_paged_kv_cache,
    prefill_attention_engages,
)
from ..models.deepseek import (
    ROUTED_COUNTS,
    TILE_COUNTS,
    count_names,
    forward_counted,
    init_latent_cache,
)
from ..models.hybrid import (
    block_sparse_engages,
    init_hybrid_cache,
    ring_attention_engages,
    state_leaves,
)
from ..ops import blocked_attention, delta_rule, pallas_attention
from ..quants.packed import q40_at_rest
from ..telemetry.logs import log_event
from ..telemetry import names
from ..telemetry.names import SCOPE_CARRY, SCOPE_HEAD, SCOPE_SAMPLER
from ..utils import faults
from .kvpool import DEFAULT_MAX_PARKED, DEFAULT_PAGE_SIZE, KVPagePool
from .spec import SPEC_DRAFT

# rungs by what a prefill half costs: flat under ~100 rows (none below 64), by the row above (gaps of 2-4x)
DEFAULT_PREFILL_BUCKETS = (64, 256, 512, 1024)

# host-swap transfer batch: pages moved per device dispatch by the
# gather/scatter swap programs (fixed operand shape = ONE compile each;
# short batches pad by repeating the first page — duplicate scatter
# indices carrying identical values are deterministic, and the pool
# axis has no sentinel page to park padding on)
_SWAP_BATCH = 8

# THE top-p default for every sampling surface (engine wrappers, scheduler
# batch vectors, control-plane packet normalization, Request): one constant,
# so a future default change cannot desync the compiled-step operands from
# the scheduler's per-lane vectors (they must be byte-identical for stream
# identity across the sync/multi/pipelined paths)
DEFAULT_TOPP = 0.9

# bounded in-flight ring for the async decode pipeline (--pipeline-depth):
# at most this many dispatched-but-unconsumed steps. 2 = classic one-step
# lag (consume step k while step k+1 runs); 0/1 disables pipelining.
DEFAULT_PIPELINE_DEPTH = 2


def _step_program(width_arg: str | None = None, width: int | None = None):
    """Wraps a step program's whole body in its ``dlstep.*`` class
    (telemetry/names.py ``STEP_PROGRAMS``, by the function's name), under
    ``jax.jit``: every operation of the program then says in its ``op_name``
    which program it is, and for which static width it was compiled
    (``width``, or the leading axis of the argument named ``width_arg``: a
    shape, so no operand and no compile of its own)."""

    def deco(fn):
        family = names.STEP_PROGRAMS[fn.__name__]
        i = None if width_arg is None else fn.__code__.co_varnames.index(width_arg)

        @wraps(fn)
        def classed(*args):
            w = width if i is None else args[i].shape[0]
            with jax.named_scope(names.step_class(family, w)):
                return fn(*args)

        return classed

    return deco


def _ordered_key(z):
    """float32 -> uint32 with the floats' order (-0.0 and 0.0 one key): the
    bit pattern with the sign bit set for z >= 0 and all bits flipped for
    z < 0, so -inf (a grammar-masked token) is the least key of a row."""
    b = jax.lax.bitcast_convert_type(z, jnp.uint32)
    k = jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))
    return jnp.where(z == 0.0, jnp.uint32(0x80000000), k)


def _count_before(mask):
    """[V] bool -> [V] float32: how many True entries lie strictly before
    each position. Rows of 128 (one MXU tile): within a row one product
    with a strictly triangular 0/1 matrix (exact: 0/1 operands, sums under
    128), across rows a running sum of V/128 row totals. (`jnp.cumsum` over
    the whole vocabulary is 0.6 ms at 32 x 152064 on a v5e, this form under
    0.1.)"""
    v, w = mask.shape[0], 128
    rows = -(-v // w)
    m = jnp.pad(mask, (0, rows * w - v)).reshape(rows, w).astype(jnp.float32)
    before_in_row = jnp.triu(jnp.ones((w, w), jnp.float32), 1)
    totals = jnp.sum(m, axis=1)
    rows_before = jnp.cumsum(totals) - totals
    return (m @ before_in_row + rows_before[:, None]).reshape(-1)[:v]


def nucleus_keep(z, topp):
    """The top-p nucleus of one row WITHOUT an order: ``z`` [V] float32 the
    logits over the temperature, p = softmax(z); returns the [V] bool
    kept set a stable descending sort, cumulative sum and
    ``(csum - p) < topp`` cut-off give, for a nucleus of any width.

    A token is kept iff the mass strictly ahead of it is under ``topp``.
    G(v) = the mass of tokens with z > v falls as v rises, so the kept set
    is ``z >= v*`` for the least value v* of the row with G(v*) < topp:
    found exactly by bisection over the 32 bits of an order-preserving key
    (32 passes of one compare, select and row sum; a fixed summation tree
    with monotone rounding keeps G monotone, so the search ends on a value
    of the row and not on a tolerance). Ties at v* as the stable sort has
    them: the tie of rank j by token id is kept iff G(v*) + j * p(v*) <
    topp. ``topp`` <= 0 or >= 1 keeps every token with p > 0, by that test
    and not by comparing a rounded sum with 1.0."""
    key, p = _ordered_key(z), jax.nn.softmax(z)

    def mass_above(v):
        return jnp.sum(jnp.where(key > v, p, 0.0))

    def _bit(i, v):
        bit = jnp.uint32(0x80000000) >> i.astype(jnp.uint32)
        # the largest key with v's leading bits and a 0 here: if the mass
        # above it is already under topp, v* is at or below it
        low = mass_above(v | (bit - jnp.uint32(1))) < topp
        return jnp.where(low, v, v | bit)

    v = jax.lax.fori_loop(0, 32, _bit, jnp.uint32(0))
    tie = key == v
    ahead = mass_above(v) + _count_before(tie) * jnp.max(jnp.where(tie, p, 0.0))
    keep = (key > v) | (tie & (ahead < topp))
    return jnp.where((topp <= 0.0) | (topp >= 1.0), p > 0.0, keep)


# EXACT on-device top-p, with no order. The rule, one function for
# every step family (decode, pipelined, fused, verify, multi-step,
# the prefill boundary token, `sample_token`):
#   1. z = row / max(temp, 1e-6) in float32, p = softmax(z) over the
#      WHOLE vocabulary (a grammar-masked token has p = 0);
#   2. a token is kept iff the mass strictly ahead of it is under
#      top_p: `nucleus_keep` (above) finds the edge value
#      by a threshold search over the unsorted row, ties at the edge
#      by token id as a stable sort has them;
#   3. top_p <= 0 or >= 1 keeps every token with p > 0 (the sorted
#      form compared a rounded running sum with 1.0 there and lost
#      80-320 tokens of a 152064-token tail);
#   4. fold_in(PRNGKey(seed), pos), then a categorical draw over the
#      kept tokens in vocabulary order; temp == 0 returns `greedy`.
# No truncation class exists, wide-nucleus / high-temperature
# requests sample on device like everyone else, and no step program
# holds a sort of the vocabulary (tests/test_sampler_no_sort.py).
# The host Sampler survives only as the host_sampling=True escape
# hatch.
def _sample_lane(row, temp, topp, seed, pos, greedy):
    """Exact nucleus sample for one lane, on device: softmax over
    the whole row, the kept set by `nucleus_keep`, a categorical
    draw over the kept tokens in vocabulary order.

    The kept set is the reference Sampler's sort→cumsum→cutoff set
    (src/tokenizer.cpp:416-457) for any (temp, topp); only the RNG
    differs (fold_in(seed, pos) + categorical here vs xorshift64*
    there — pinned by tests/test_sampler_parity.py). Deterministic
    per (seed, position): seeded runs reproduce. The Gumbel noise
    is attached to a token's ID; builds that sorted the row first
    attached it to the token's RANK, so a (seed, position) yields
    another, equally valid, token than it did there (a journal
    written by such a build replays to other tokens)."""
    z = row.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
    keep = nucleus_keep(z, topp)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
    # z is log p plus a constant of the row: the same distribution
    choice = jax.random.categorical(key, jnp.where(keep, z, -jnp.inf))
    return jnp.where(temp == 0.0, greedy, choice.astype(jnp.int32))


# The rows the search walks 33 times have to stay in fast memory: a row's
# key (uint32) and p (float32) are 8 bytes a token, and on a v5e XLA holds
# 32 x 152064 of them (38.9 MB) or 64 x 65536 (33.6 MB: 3.5 us a pass) where
# 256 x 65536 at once (134 MB) streams the keys from HBM on every pass
# (178 us). Groups of 128 x 65536 stay resident too and are no faster.
SAMPLER_GROUP_BYTES = 40 << 20


def sampler_group(rows: int, vocab: int) -> int:
    """How many rows the sampler takes at once: the largest divisor of
    ``rows`` whose keys and probabilities fit ``SAMPLER_GROUP_BYTES`` (one
    row where even one does not). Static shapes only, so every step program
    of an engine decides alike."""
    fit = max(1, SAMPLER_GROUP_BYTES // (8 * vocab))
    return max(g for g in range(1, min(rows, fit) + 1) if rows % g == 0)


def sample_lanes(rows, temps, topps, seeds, positions, greedy):
    """``_sample_lane`` over ``rows`` [n, V] and the lanes' [n] operands, in
    groups of ``sampler_group`` rows, one group after another (``lax.map``
    is sequential: two groups' rows are never live together). A lane's
    arithmetic is the same expression over the same row whatever the group;
    with one group this IS ``jax.vmap(_sample_lane)``."""
    lanes = jax.vmap(_sample_lane)
    operands = (rows, temps, topps, seeds, positions, greedy)
    n, vocab = rows.shape
    group = sampler_group(n, vocab)
    if group == n:
        return lanes(*operands)
    grouped = tuple(a.reshape(n // group, group, *a.shape[1:]) for a in operands)
    return jax.lax.map(lambda xs: lanes(*xs), grouped).reshape(n)


@dataclass
class EngineStats:
    """Per-call timing + transfer counters — the analogue of the reference's
    per-step-type totalTime[] and socket byte counters (SURVEY.md §5.1,
    src/dllama.cpp:54-64, src/nn/nn-network.cpp:493-508)."""

    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_tokens: int = 0
    # rows the prefill halves COMPUTED: a chunk rides the smallest bucket
    # that holds it (bucket_for) and the program computes every row of the
    # bucket, so this grows by the bucket where prefill_tokens grows by the
    # chunk; 1 - prefill_tokens / prefill_bucket_rows is the padding
    prefill_bucket_rows: int = 0
    decode_steps: int = 0
    host_bytes_in: int = 0  # device->host logits/token traffic
    spec_steps: int = 0  # speculative verify steps (one per batched call)
    # maintained by the consuming loops (scheduler / SpecStream), since the
    # engine cannot know how many verified tokens the caller commits.
    # DRAFTED lanes only (draft_len > 0), consumed tokens only — so
    # emitted/lane_steps reads as acceptance in [1, K+1]:
    spec_emitted: int = 0  # tokens consumed from spec steps, drafted lanes
    spec_lane_steps: int = 0  # (drafted lane, spec-step) pairs
    prefix_hits: int = 0  # admissions that reused another lane's KV prefix
    prefix_tokens_saved: int = 0  # prompt tokens NOT re-prefilled
    multi_dispatches: int = 0  # decode_multi calls (each = h decode steps,
    # ONE host round-trip — the serving loop's per-token dispatch amortizer)
    # zero-flush serving (decode_spec_pipelined / decode_spec_prefill_fused):
    spec_pipelined_steps: int = 0  # spec verify steps dispatched INSIDE the
    # pipelined ring (each also counts in spec_steps/pipeline_dispatches)
    spec_accept_hist: dict = field(default_factory=dict)  # device accept
    # count -> occurrences, DRAFTED lanes only (0 = no draft survived the
    # carry-alignment gate, K = full acceptance); written by the consuming
    # scheduler, which is the only layer that knows which lanes drafted
    host_exact_lanes: int = 0  # lanes routed through the host Sampler
    # (host_sampling=True escape hatch only — the on-device sampler is
    # full-vocab exact, so this reads 0 in default serving)
    # async decode pipeline (decode_pipelined / pipeline_consume):
    overlap_s: float = 0.0  # host-side time between a step's dispatch and
    # the start of its (lagged) readback — work the device execution hid,
    # which the synchronous path would have serialized
    pipeline_dispatches: int = 0  # pipelined steps dispatched
    pipeline_flushes: int = 0  # chains aborted before their lanes finished
    # (speculation/host-exact/stop flush); a natural end-of-chain drain
    # does not count, and with fused prefill an admission does not either,
    # so steady-state decode — churn included — reads 0
    pipeline_depth_hist: dict = field(default_factory=dict)  # ring depth
    # right after each dispatch -> count (how deep the overlap actually ran)
    # the dry-dispatch witness, kept by the scheduler's loop: dispatches it
    # made when the device had already finished everything in flight
    # (pipeline_ready() just before the hand-over; a chain's first fill is
    # not counted, nothing was running to run dry), and for those the host's
    # seconds from the readback before to the dispatch's return: the most the
    # device can have stood idle for want of work, on the host's clock
    pipeline_dry_dispatches: int = 0
    pipeline_dry_s: float = 0.0
    # live lanes summed over the pipelined dispatches: over
    # pipeline_dispatches x lanes, how full the decode batch ran
    live_lane_steps: int = 0
    # stall-free admissions (decode_prefill_fused):
    fused_steps: int = 0  # fused prefill+decode dispatches (each advances
    # every generating lane one token AND consumes one prompt chunk)
    admission_stall_s: float = 0.0  # host time generating lanes spent
    # stalled behind admission work (sync prefill chunks, or in-chain lane
    # claims taken while the ring was empty); ~0 when fused dispatches
    # carry the admission under a full ring
    fused_bucket_hist: dict = field(default_factory=dict)  # prefill bucket
    # -> fused dispatches that carried a chunk of that bucket
    # estimated per-step collective payload (bytes/chip), from the compiled
    # decode program's post-SPMD HLO — the Sent/Recv kB analogue on a mesh
    sync_bytes_per_decode: int = 0
    sync_collectives_per_decode: int = 0
    # cumulative estimated collective payload (bytes/chip) dispatched with
    # decode-FAMILY steps (sync/multi/spec/pipelined/fused), i.e.
    # sync_bytes_per_decode accrued per chained step — feeds /stats and the
    # dllama_sync_bytes_total counter on /metrics. Prefill-only dispatches
    # are not counted (their program's traffic differs from the decode
    # estimate); 0 off-mesh or before collective_stats() runs.
    sync_bytes_total: int = 0
    # failure containment (multihost.worker_serve): supervised-restart and
    # classified replay-protocol-error counts on THIS process, so pod
    # worker health is a stats read, not a stderr grep
    worker_restarts: int = 0
    worker_replay_errors: int = 0
    # grammar-constrained decoding (grammar/): admissions that attached a
    # compiled automaton, and dispatches that carried at least one
    # constrained lane (every step family threads the mask; these count
    # the ones where it actually bit)
    grammar_lanes: int = 0
    grammar_masked_steps: int = 0
    # decode attention's reads of the KV cache, in rows of one layer's K (or
    # V) plane, summed over dispatched decode steps (fused steps' decode
    # halves included; verify steps, which are wider than one row, not).
    # Kept by the scheduler from the positions the host tracks: `read` is
    # what the step fetches (whole blocks up to each live lane's row where
    # the in-place kernel engages, ops/pallas_attention.py; the whole plane
    # where it does not), `whole` what reading whole planes fetches
    # (lanes x seq_len a step). Their ratio says how much of a gain is the
    # traffic's (short lanes) and how much the kernel's
    attn_kv_rows_read: int = 0
    attn_kv_rows_whole: int = 0
    # a routed FFN's reads of its expert stacks (models/deepseek.py), summed
    # over the consumed decode steps (fused steps' decode halves included).
    # Counted ON THE DEVICE from the expert ids and brought back in the
    # step's packed token readback, so no sync is added: `read` is distinct
    # (layer, expert) slabs a step fetched of ONE expert matrix (each of w1,
    # w3, w2 reads that many), `whole` routed layers x experts a step (what a
    # sweep of every expert reads), `assignments` live rows x experts a token
    # x routed layers. All 0 for a model without routed layers
    moe_slabs_read: int = 0
    moe_slabs_whole: int = 0
    moe_assignments: int = 0
    # how full the grouped kernel's tiles are, over the decode steps AND the
    # prompt chunks that rode them (a fused step's prefill half): `tile_pairs`
    # the (row, expert) pairs that took a row, `tile_rows` the rows of the
    # tiles they sat in (used tiles x their height: what each of the three
    # products multiplied)
    moe_tile_pairs: int = 0
    moe_tile_rows: int = 0
    # a held share of the routed experts (config.experts_held_count; 0 and 0
    # where every expert is held): the experts a layer holds here (fixed at
    # start-up, kept by reset()), and the (row, expert) pairs of the decode
    # steps whose expert is another chip's: they fetch nothing and add
    # nothing, and moe_assignments counts only the pairs that did fetch
    moe_experts_held: int = 0
    moe_rows_unheld: int = 0
    # learned sparse attention (config.index_topk; all 0 elsewhere), counted
    # on the device and brought back like the routed counts: index keys the
    # decode steps scored (lanes x layers x rows held) and latent rows their
    # attention then read (at most index_topk a lane a layer). With an
    # indexer the scheduler's attn_kv_rows_read counts the rows CHOSEN (of
    # one layer, as ever) and attn_kv_rows_whole the rows HELD by the live
    # lanes, so their ratio is what the selection leaves of the context
    indexer_rows_scored: int = 0
    sparse_rows_selected: int = 0
    # a model whose lanes carry a state overwritten in place beside the KV
    # cache (models/hybrid.py; all 0 for every other model): the bytes of
    # that state over all lanes (fixed at start-up, kept by reset());
    # prompt chunks dispatched at position 0, which read zero state whatever
    # the lane held (one an admission: nothing is cleared on the device);
    # admissions that shared a long enough prefix with a resident lane and
    # were prefilled whole all the same, because a copied lane would carry
    # the source's state at ITS last position
    recurrent_state_bytes: int = 0
    state_zero_starts: int = 0
    prefix_reuse_declined: int = 0
    # selective state-space layers (config.n_ssm_layers; all 0 elsewhere):
    # (live lane, layer) pairs whose running sum the decode steps advanced
    # (counted by the scheduler from its own lane positions), and prompt rows
    # through the chunked scan, summed over those layers: the real ones, and
    # every row of the bucket the chunk rode (the program computes them all)
    ssm_lane_steps: int = 0
    ssm_rows_scanned: int = 0
    ssm_rows_computed: int = 0
    # linear-attention and block-sparse layers (config.n_linear_layers,
    # config.n_sparse_layers; all 0 elsewhere), kept by the scheduler from its
    # own lane positions over the decode steps: the bytes of float32 matrix
    # state the steps read and wrote (a live lane's, every linear layer, in
    # and out); blocks of sparse_block_size positions a sparse layer's kv head
    # attended and blocks it held, summed over live lanes and steps (ONE
    # layer's, ONE kv head's: every layer and head has the same counts); and
    # (live lane, step) pairs at or past sparse_dense_len, whose rows chose.
    # By the engine, as ssm_rows_computed: prompt rows through the chunk form
    # summed over the linear layers, every row of the bucket the chunk rode
    linear_state_bytes_moved: int = 0
    linear_rows_computed: int = 0
    # delta-rule layers (config.n_delta_layers; 0 elsewhere), counted as the
    # two above: bytes of float32 matrix state the decode steps read and
    # wrote (a live lane's, every delta layer, in and out), by the scheduler;
    # (row, layer) pairs through the chunk form, every row of the bucket a
    # chunk rode, by the engine
    delta_state_bytes_moved: int = 0
    delta_rows_computed: int = 0
    attn_blocks_read: int = 0
    attn_blocks_held: int = 0
    sparse_lane_steps: int = 0
    # window attention layers (config.n_window_layers; all 0 elsewhere), in
    # rows of ONE window layer's ring, as attn_kv_rows_* are rows of one
    # full-context layer's plane (and stay so: in a model with both kinds
    # that pair counts the full-context kind alone). Kept by the scheduler
    # from its own lane positions over the decode steps: `window_rows_read`
    # what a window layer fetched (whole blocks that hold (pos - window, pos]
    # where the kernel engages; the whole ring where it does not),
    # `full_rows_read` what a full-context layer fetched (attn_kv_rows_read
    # under the name of its kind), `window_rows_plane` what a window layer
    # would have fetched had it kept a plane and read it as the full-context
    # layers read theirs. And the prefill attention computed a key block at a
    # time (ops/blocked_attention.py; 0 where every chunk's scores are dense),
    # in (query row, key block) pairs summed over the layers: what the loops
    # ran (every row of the bucket), and the least the mask allows (a real
    # row's blocks that hold a position it reads)
    attn_window_rows_read: int = 0
    attn_full_rows_read: int = 0
    attn_window_rows_plane: int = 0
    # and the least either kind's read needs, by the same arithmetic: the rows
    # a live lane's step attends, `pos + 1` of a full-context layer's plane and
    # `min(pos + 1, window)` of a window layer's ring (whole blocks are what
    # the `*_rows_read` pair above counts)
    attn_full_rows_needed: int = 0
    attn_window_rows_needed: int = 0
    prefill_attn_blocks_visited: int = 0
    prefill_attn_blocks_causal: int = 0
    # compile stability (analysis/jitcheck.py, ISSUE 15): XLA backend
    # compiles observed AFTER warmup_engine armed the recompile witness —
    # the machine-checked form of "one compiled program per (family,
    # bucket), compiled only at warmup". Must read 0 in steady serving;
    # any bump means an unwarmed family or an aval-changing operand
    # rebuild stalled every lane mid-service. NOT cleared by reset():
    # like sync_bytes_per_decode it describes the process since warmup,
    # not a stats window — a window reset must not hide a recompile.
    jit_compiles_after_warmup: int = 0
    # writers (engine hot paths, scheduler counters) hold this around their
    # multi-field bumps; snapshot()/reset() hold it while copying, so a
    # /stats read sees one consistent point in time instead of field-by-field
    # values racing the batching thread
    lock: threading.Lock = field(
        # built via make_lock so the runtime lock-order witness
        # (DLLAMA_LOCKCHECK=1) can wrap it; literal cross-checked by dlint
        default_factory=lambda: make_lock("EngineStats.lock"),
        repr=False, compare=False,
    )

    # dlint guarded-by declaration (analysis/lock_check.py): every counter
    # above may only be read or written inside `with <stats>.lock:` (or in
    # __init__ / *_locked methods). Machine-checked by `make lint` — a new
    # unlocked bump anywhere in the package fails tier-1. Not annotated,
    # so the dataclass does not treat it as a field.
    _dlint_guarded_by = {
        ("lock",): (
            "prefill_s", "decode_s", "prefill_tokens", "prefill_bucket_rows",
            "decode_steps",
            "host_bytes_in", "spec_steps", "spec_emitted", "spec_lane_steps",
            "prefix_hits", "prefix_tokens_saved", "multi_dispatches",
            "spec_pipelined_steps", "spec_accept_hist", "host_exact_lanes",
            "overlap_s", "pipeline_dispatches", "pipeline_flushes",
            "pipeline_depth_hist",
            "pipeline_dry_dispatches", "pipeline_dry_s", "live_lane_steps",
            "fused_steps", "admission_stall_s", "fused_bucket_hist",
            "sync_bytes_per_decode", "sync_collectives_per_decode",
            "sync_bytes_total", "worker_restarts", "worker_replay_errors",
            "grammar_lanes", "grammar_masked_steps",
            "attn_kv_rows_read", "attn_kv_rows_whole",
            "moe_slabs_read", "moe_slabs_whole", "moe_assignments",
            "moe_tile_pairs", "moe_tile_rows",
            "moe_experts_held", "moe_rows_unheld",
            "indexer_rows_scored", "sparse_rows_selected",
            "recurrent_state_bytes", "state_zero_starts", "prefix_reuse_declined",
            "ssm_lane_steps", "ssm_rows_scanned", "ssm_rows_computed",
            "linear_state_bytes_moved", "linear_rows_computed",
            "delta_state_bytes_moved", "delta_rows_computed", "attn_blocks_read",
            "attn_blocks_held",
            "sparse_lane_steps",
            "attn_window_rows_read", "attn_full_rows_read", "attn_window_rows_plane",
            "attn_full_rows_needed", "attn_window_rows_needed",
            "prefill_attn_blocks_visited", "prefill_attn_blocks_causal",
            "jit_compiles_after_warmup",
        ),
    }

    def _counters(self) -> dict:
        # dict-valued counters (the depth histogram) are copied, not
        # aliased: a snapshot must not mutate under its reader's feet
        return {
            k: (dict(v) if isinstance(v, dict) else v)
            for k, v in self.__dict__.items()
            if k != "lock"
        }

    def snapshot(self) -> dict:
        """Consistent point-in-time copy of every counter (one lock hold)."""
        with self.lock:
            return self._counters()

    def reset(self) -> "EngineStats":
        with self.lock:
            snap = EngineStats(**self._counters())
            self.prefill_s = self.decode_s = self.overlap_s = 0.0
            self.prefill_tokens = self.prefill_bucket_rows = 0
            self.decode_steps = self.host_bytes_in = 0
            self.spec_steps = self.spec_emitted = self.spec_lane_steps = 0
            self.prefix_hits = self.prefix_tokens_saved = 0
            self.multi_dispatches = 0
            self.spec_pipelined_steps = self.host_exact_lanes = 0
            self.spec_accept_hist = {}
            self.pipeline_dispatches = self.pipeline_flushes = 0
            self.pipeline_depth_hist = {}
            self.pipeline_dry_dispatches = self.live_lane_steps = 0
            self.pipeline_dry_s = 0.0
            self.fused_steps = 0
            self.admission_stall_s = 0.0
            self.fused_bucket_hist = {}
            self.sync_bytes_total = 0
            self.worker_restarts = self.worker_replay_errors = 0
            self.grammar_lanes = self.grammar_masked_steps = 0
            self.attn_kv_rows_read = self.attn_kv_rows_whole = 0
            self.moe_slabs_read = self.moe_slabs_whole = self.moe_assignments = 0
            self.moe_tile_pairs = self.moe_tile_rows = 0
            self.moe_rows_unheld = self.indexer_rows_scored = self.sparse_rows_selected = 0
            self.state_zero_starts = self.prefix_reuse_declined = 0
            self.ssm_lane_steps = self.ssm_rows_scanned = self.ssm_rows_computed = 0
            self.linear_state_bytes_moved = self.attn_blocks_read = self.linear_rows_computed = 0
            self.delta_state_bytes_moved = self.delta_rows_computed = 0
            self.attn_blocks_held = self.sparse_lane_steps = 0
            self.attn_window_rows_read = self.attn_full_rows_read = 0
            self.attn_window_rows_plane = 0
            self.attn_full_rows_needed = self.attn_window_rows_needed = 0
            self.prefill_attn_blocks_visited = self.prefill_attn_blocks_causal = 0
            # per-decode sync_* stay: they describe the compiled program,
            # not a window; jit_compiles_after_warmup stays: it describes
            # compile stability since warmup, and a window reset hiding a
            # mid-serving recompile would defeat the witness
        return snap

    def preserved(self):
        """Context manager: restore all counters on exit — for probes and
        warmup, whose fake engine calls must not pollute serving totals."""
        import contextlib

        @contextlib.contextmanager
        def cm():
            snap = self.snapshot()
            try:
                yield self
            finally:
                with self.lock:
                    self.__dict__.update(snap)

        return cm()


class InferenceEngine:
    # dlint resource-lifecycle declaration (analysis/resourcemodel.py):
    # the engine's paged façade mirrors the pool's lane-page ownership —
    # ``paged_admit`` acquires (pool admit + device table write as one
    # unit), ``paged_finish``/``paged_reset`` give it back. Same kind as
    # the pool's own vocabulary so wrappers of either balance.
    _dlint_acquires = {"kv-page": ("paged_admit",)}
    _dlint_releases = {"kv-page": ("paged_finish", "paged_reset")}

    # dlint device-affinity declaration: these methods touch pytrees the
    # compiled step families DONATE (engine.cache, the paged table, the
    # grammar slab). Off the batching loop they race the live chain —
    # the step that is about to consume the buffer they mutate (the race
    # PR 16 caught live). Legal callers: the loop-thread closure
    # (_dlint_loop_roots on the scheduler) or a closure handed to
    # scheduler.run_device_op(). Checked by dlint device-affinity.
    _dlint_device_affine = (
        "apply_paged_admit", "copy_lane", "paged_unmap_all",
        "export_kv_page", "import_kv_page",
        "swap_out_pages", "swap_in_pages",
    )

    def __init__(
        self,
        config: LlamaConfig,
        params: LlamaParams,
        n_lanes: int = 8,
        prefill_buckets: tuple[int, ...] = DEFAULT_PREFILL_BUCKETS,
        cache_dtype=None,
        emulate_q80_activations: bool = False,
        mesh=None,
        replicate_outputs: bool = False,
        q80_sync: bool = False,
        pipeline_depth: int | None = None,
        paged_kv: bool = False,
        kv_page_size: int = DEFAULT_PAGE_SIZE,
        kv_pool_pages: int | None = None,
        kv_max_parked: int = DEFAULT_MAX_PARKED,
        kv_host_bytes: int = 0,
        grammar_slab_states: int | None = None,
        grammar_slab_edges: int | None = None,
    ):
        """``paged_kv=True`` stores KV as a pooled set of fixed-size pages
        behind a per-lane page table (runtime/kvpool.py) instead of
        contiguous per-lane planes: prefix sharing becomes a refcount
        bump on the shared pages (zero HBM copies; ``copy_lane`` is the
        contiguous path's primitive and is refused here), divergence is
        served by a single-page copy-on-write, and finished sessions
        park their sharable pages so resident sessions exceed lanes.
        Token streams are byte-identical to the contiguous layout
        (pinned). ``kv_page_size`` is the page granularity in tokens
        (power of two; shrunk to fit short seq_len configs);
        ``kv_pool_pages`` sizes the pool (default: the contiguous
        layout's exact footprint, n_lanes x blocks-per-full-lane);
        ``kv_max_parked`` bounds parked sessions (LRU-evicted under pool
        pressure); ``kv_host_bytes`` budgets the host-RAM swap tier
        between "parked" and "dropped" (0 disables it, restoring
        drop-to-rebuild bit-for-bit — see ``kvpool.HostTier``)."""
        # The step programs' ``dl.*`` scopes (telemetry/names.py) are HLO
        # metadata, which the persistent compile cache leaves out of its
        # key by default: an executable cached before a scope was added,
        # moved or renamed would be served as it is, and its device trace
        # would carry the old names (none at all, from a cache older than
        # the scopes). A trace is read by those names, so they are part of
        # what a cached program is; the price is a recompile where only
        # source locations moved.
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        self.config = config
        # the Q40 scale stacks whose tiles the kernel reads in place, as the
        # int16 bits it reads (quants/packed.py): made once, HERE and nowhere
        # else, for a tree that arrives with float16 scales (the loaders', a
        # generator's). Those float16 stacks are the caller's to keep or drop
        # (app/runtime_setup.load_stack drops them), not held here; a leaf
        # whose plane is sliced out a call stays as it arrived.
        from ..ops.pallas_q40 import reads_scales_in_place

        self.params = q40_at_rest(params, only=reads_scales_in_place)
        self.n_lanes = n_lanes
        self.mesh = mesh
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= config.seq_len
        ) or (min(16, config.seq_len),)
        if cache_dtype is None:
            # bf16 KV on TPU (half the HBM of f32; the reference shards its
            # f32 KV only because RPi has no bf16 — src/nn/nn-core.cpp:198-205);
            # f32 on CPU where the parity oracle runs
            cache_dtype = (
                jnp.bfloat16 if jax.devices()[0].platform == "tpu" else jnp.float32
            )
        self.cache_dtype = cache_dtype
        # What frames a lane as pages of a K/V pair of heads a layer, moves
        # such pages between tiers or replicas, or partitions by head would
        # run wrongly for a block that keeps another state on one device:
        # refused by name, here, not in the middle of a step
        unserved = None
        if config.latent_attention:
            # models/deepseek.py: one latent row a token
            unserved = (
                f"a latent-attention model (kv_lora_rank {config.kv_lora_rank}) "
                "keeps one latent cache row a token on one device",
                "a page is framed as a K/V pair of heads",
                "the block's latent cache and expert stacks have no sharding",
            )
        elif config.layer_kinds:
            # models/hybrid.py: a stack a kind of layer, and (with a conv
            # layer) a state overwritten in place
            unserved = (
                "a model with a per-layer pattern of mixers "
                f"({config.n_conv_layers} conv, {config.n_ssm_layers} state-space, "
                f"{config.n_linear_layers} linear-attention, "
                f"{config.n_delta_layers} delta-rule, "
                f"{config.n_attention_layers} attention layers) keeps a stack a kind "
                "on one device",
                "a lane's state is not pages of a K/V pair a layer",
                "the block's state and expert stacks have no sharding",
            )
        if unserved is not None:
            subject, why_pages, why_mesh = unserved
            refused = [
                (paged_kv, "the paged KV pool (--paged-kv on), and with it "
                           "prefix page sharing, KV-page transfer, migration "
                           f"tickets and a prefill/decode role: {why_pages}"),
                (kv_host_bytes > 0, "the host KV tier (--kv-host-bytes): it "
                                    "swaps the pool's pages"),
                (mesh is not None, f"a mesh (--workers): {why_mesh}"),
            ]
            for hit, what in refused:
                if hit:
                    raise ValueError(f"{subject} and does not serve {what}")
        init_contiguous = (
            # a window layer's ring holds the window and the widest chunk
            partial(init_hybrid_cache, max_chunk=self.prefill_buckets[-1])
            if config.layer_kinds
            else init_latent_cache if config.latent_attention else init_kv_cache
        )
        if paged_kv:
            if mesh is not None and (
                dict(mesh.shape).get("dp", 1) > 1
                or dict(mesh.shape).get("sp", 1) > 1
            ):
                # the pool is ONE global resource every lane maps into
                # (parallel/sharding.paged_cache_shardings): under dp it
                # would replicate — sized to the contiguous layout's
                # WHOLE footprint, a dp-fold HBM regression — and sp has
                # no per-lane S axis to shard. Serving pod meshes are
                # pure-TP; refuse the silent misconfiguration.
                raise ValueError(
                    "paged_kv requires a pure-TP mesh (dp=1, sp=1): the "
                    "page pool replicates over dp and cannot shard over "
                    "sp — use --paged-kv off on dp/sp meshes"
                )
            # paged pool: page granularity shrinks to fit short contexts
            # (tiny test configs) but stays a power of two; the default
            # pool size is the contiguous layout's exact HBM footprint —
            # oversubscription comes from sessions reserving only what
            # they can use (prompt + max_tokens), not a bigger pool
            # one construction recipe (validation, power-of-two shrink,
            # contiguous-footprint default), shared with the mock so the
            # scheduler-level tests exercise the identical pool geometry
            self.kvpool = KVPagePool.for_seq_len(
                config.seq_len, n_lanes, page_size=kv_page_size,
                pool_pages=kv_pool_pages, max_parked=kv_max_parked,
                host_bytes=kv_host_bytes,
            )
            # swap-tier traffic counters: single-writer (every swap op
            # runs on the scheduler loop thread / device-op funnel),
            # read lock-free by pool_stats() from HTTP threads
            self.swap_ins = 0
            self.swap_outs = 0
            self.swap_in_bytes = 0
            self.swap_out_bytes = 0
            self.swap_in_ms = 0.0
            bs = self.kvpool.page_size
            n_pages = self.kvpool.n_pages
            # dlint: ok[host-sync] host int lists -> the numpy table mirror; no device value involved
            self._host_tables = np.asarray(
                [self.kvpool.table_row([])] * n_lanes, np.int32
            )
            init_fn = partial(
                init_paged_kv_cache, config, n_lanes, n_pages, bs,
                n_blocks=self.kvpool.blocks_per_lane, dtype=cache_dtype,
            )
            if mesh is not None:
                from ..parallel.sharding import paged_cache_shardings

                shardings = paged_cache_shardings(mesh)
                self.cache = jax.jit(
                    init_fn, out_shardings=shardings
                )()
                # every table replacement must carry this sharding (see
                # _replace_leaf, THE sanctioned constructor): a bare
                # jnp.asarray leaf would change the compiled programs'
                # input aval (recompile per admission on a single-host
                # mesh; incompatible-devices failure on a multi-process
                # pod) — machine-checked by dlint's jit-stability
                self._table_sharding = shardings.table
            else:
                self.cache = init_fn()
                self._table_sharding = None
        elif mesh is not None:
            self.kvpool = None
            # materialize the cache already placed (lanes over dp, sequence
            # over sp, kv heads over tp — parallel/sharding.cache_shardings);
            # round 2 left serving caches unplaced, so GSPMD chose for us
            from ..parallel.sharding import cache_shardings

            self.cache = jax.jit(
                partial(init_kv_cache, config, n_lanes, dtype=cache_dtype),
                out_shardings=cache_shardings(mesh),
            )()
        else:
            self.kvpool = None
            self.cache = init_contiguous(config, n_lanes, dtype=cache_dtype)
        self.stats = EngineStats()
        # bytes of per-lane state overwritten in place (0: none)
        self.lane_state_bytes = sum(leaf.nbytes for leaf in state_leaves(self.cache))
        self.stats.recurrent_state_bytes = self.lane_state_bytes
        # routed layers x experts: what a decode step adds to moe_slabs_whole
        # (0: no routed layers, and no counts ride the token readback)
        self.moe_slabs_per_step = config.n_routed_layers * config.n_experts
        self.stats.moe_experts_held = config.experts_held_count
        # what the counts that ride a decode step's token readback are, in
        # their order (models/deepseek.count_names); () where none ride it
        self._count_names = count_names(config) if config.latent_attention else (
            ROUTED_COUNTS if config.n_routed_layers else ())
        # an indexer's selection is made for one new row a lane or for a
        # prompt chunk; a verify step's rows are not served (declined by
        # name: path_facts, the scheduler's start-up line)
        if config.sparse_attention:
            self.supports_speculative = self.supports_spec_pipelined = False
        # a verify step advances a lane by rows it may reject, and a state
        # overwritten in place cannot give them back: a model with one is
        # served without speculation (the scheduler and warmup_engine ask;
        # the verify entry points refuse)
        if config.recurrent_state:
            self.supports_speculative = self.supports_spec_pipelined = False
        # cache rows a block of the in-place decode attention fetches; None
        # where decode steps read whole planes (the scheduler's
        # attn_kv_rows_* counters ask)
        self.decode_attention_block = (
            pallas_attention.block_rows(config.latent_attention)
            if decode_attention_engages(
                self.cache, mesh, config.n_heads, config.n_kv_heads,
                latent=config.latent_attention)
            else None
        )
        # the same for a window layer's ring (None: no window layers, or
        # their decode steps read whole rings), and the ring's rows a lane
        self.ring_rows = self.cache.wk.shape[2] if config.n_window_layers else 0
        self.decode_ring_block = (
            pallas_attention.BLOCK_ROWS
            if ring_attention_engages(
                self.cache, mesh, config.n_heads, config.kv_heads(windowed=True))
            else None
        )
        # the start from which a prompt chunk takes the second-largest bucket
        # (None: never): where the full-context layers' planes are read by key
        # blocks, a chunk far into a long prompt holds the decoding lanes as
        # long as one near its start
        self.chunk_taper_start = (
            blocked_attention.taper_start(
                self.prefill_buckets, config.n_heads, config.seq_len)
            if config.layer_kinds and config.n_attention_layers and self.kvpool is None
            else None
        )
        # async decode pipeline: bounded ring of dispatched-but-unconsumed
        # steps plus the on-device token carry feeding the next dispatch
        self.pipeline_depth = (
            DEFAULT_PIPELINE_DEPTH if pipeline_depth is None
            else max(0, pipeline_depth)
        )
        # ring entries: (kind, packed device array, t_dispatched) with kind
        # "tok" ([2, n(+1)] greedy/sampled rows) or "spec" ([n(+1), K+2]
        # emitted tokens + per-lane emit count)
        self._pl_inflight: deque = deque()
        self._pl_carry = None  # [n] device int32: next feed per lane
        # [n] device int32: each lane's next WRITE position — part of the
        # carry since spec verify steps advance lanes by a per-lane accept
        # count the host only learns one step later (pos+1 generalizes to
        # pos+accepted+1). Dispatch positions with value -1 select this
        # carried position; >= 0 overrides from host metadata (parked /
        # admitting / freshly reseeded lanes).
        self._pl_carry_pos = None
        # [n] device int32: each lane's grammar-automaton state (absolute
        # slab id; 0 = FREE/unconstrained), advanced ON DEVICE by every
        # chosen token exactly like the position carry — same -1/override
        # dispatch semantics, so constrained lanes ride the zero-flush
        # chain without any host round-trip
        self._pl_carry_g = None
        # grammar slab (grammar/slab.py): fixed-capacity mask + transition
        # tables, state 0 = FREE (all-ones mask) so unconstrained lanes run
        # the identical compiled math. Device copies upload lazily on slab
        # version bumps (admissions of new schemas) — shapes never change,
        # so grammar churn can never trigger an XLA recompile.
        self.grammar_slab = GrammarSlab(
            config.vocab_size,
            n_states=grammar_slab_states or DEFAULT_SLAB_STATES,
            n_edges=grammar_slab_edges or DEFAULT_SLAB_EDGES,
        )
        self._g_dev = None
        self._g_version = -1
        self._g_vocab = None  # token piece table (grammar_init)
        self._g_vocab_key = None
        self._g_eos: tuple = ()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # the slab tables are small and read by every chip: fully
            # replicated, like the token carries
            self._g_sharding = NamedSharding(mesh, PartitionSpec())
        else:
            self._g_sharding = None

        cfg = config
        # the configuration's block (models/llama.py, or models/deepseek.py
        # for latent attention): one forward for every step family. The
        # decode steps of the pipelined chain also take its counts (a routed
        # FFN's slab reads; None for a Llama block, whose programs are then
        # what they always were)
        forward_c = forward_counted(cfg)

        def forward_n(*a, n_valid=None, **kw):
            # n_valid (a prefill chunk's real tokens) reaches a block whose
            # lanes carry a state overwritten in place, and a Llama block,
            # whose prefill attention then fetches no key block past the last
            # real row; the latent block has no use for it, and its programs
            # are what they always were. head_row (the one row a prefill half
            # keeps the logits of) every block takes: it rides kw
            if cfg.recurrent_state or not (cfg.latent_attention or cfg.layer_kinds):
                kw["n_valid"] = n_valid
            return forward_c(*a, **kw)

        def forward(*a, **kw):
            return forward_n(*a, **kw)[:2]

        def _token_rows(greedy, sampled, counts):
            # [2, n]: the step's packed token readback; a routed model's
            # counts ride it as more rows (each count in every column)
            rows = [greedy, sampled]
            if counts is not None:
                rows += [jnp.broadcast_to(c, greedy.shape) for c in counts]
            return jnp.stack(rows)

        q80 = emulate_q80_activations
        # Q80-compressed wo/w2 sync (the reference's default transport);
        # meaningful on DCN-spanning meshes where payload bytes matter
        q80s = q80_sync

        sp_mesh = mesh

        if replicate_outputs and mesh is not None:
            # multi-host: logits/greedy must come back fully replicated, or
            # no process can fetch them (a cross-host-sharded jax.Array is
            # not locally convertible; the reference instead gathers logits
            # to its root over TCP, SYNC_NODE_SLICES_EXCEPT_ROOT)
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(mesh, PartitionSpec())
            replicate = lambda x: jax.lax.with_sharding_constraint(x, rep)
        else:
            replicate = lambda x: x

        if mesh is not None:
            # mesh-native token plumbing: the on-device carry feeding the
            # next pipelined dispatch and the packed [2, n(+1)] token
            # readbacks are EXPLICITLY replicated — a few bytes per step —
            # so GSPMD can never choose a sharded layout that would splice
            # a cross-device gather between chained dispatches (the pod
            # serving path's first-dispatch stall). Logits keep the
            # replicate_outputs policy above (replicating [n, vocab] f32 is
            # an all-gather worth paying only when a host must read it).
            from jax.sharding import NamedSharding, PartitionSpec

            _tok_rep = NamedSharding(mesh, PartitionSpec())
            rep_tokens = lambda x: jax.lax.with_sharding_constraint(x, _tok_rep)
        else:
            rep_tokens = lambda x: x

        # grammar-constrained decoding (grammar/): per-state packed legal-
        # token masks + compact transitions, gathered INSIDE the compiled
        # step. ``gtab`` = (masks [S, ceil(V/32)] u32, edge_keys [E] i32
        # sorted as state*V+token, edge_next [E] i32, default_next [S]
        # i32) rides every family as an operand (device-resident, updated
        # only on schema admission); ``gs``/``g`` are per-lane automaton
        # states — 0 is the FREE state (all-ones mask, self-loop), so
        # unconstrained lanes run the identical math and their streams
        # stay byte-identical by construction.
        _g_tok_ids = jnp.arange(cfg.vocab_size, dtype=jnp.uint32)

        def _g_bits(gtab, g):
            row = gtab[0][g]  # [ceil(V/32)] uint32
            return (
                (row[_g_tok_ids >> 5] >> (_g_tok_ids & jnp.uint32(31)))
                & jnp.uint32(1)
            ).astype(jnp.bool_)

        def _g_mask_row(gtab, g, row):
            # -inf outside the state's legal set: the masked row feeds the
            # SAME argmax + full-vocab nucleus search/categorical as a free
            # row (an order-preserving key and p = 0: never kept)
            return jnp.where(_g_bits(gtab, g), row, -jnp.inf)

        _g_mask_rows = jax.vmap(_g_mask_row, in_axes=(None, 0, 0))

        def _g_next1(gtab, g, tok):
            # compact transition: sorted sparse exceptions, else the
            # state's majority target. Illegal tokens (never chosen — the
            # mask excluded them) land on the bounded default.
            keys, nxt, dflt = gtab[1], gtab[2], gtab[3]
            key = g * cfg.vocab_size + tok
            j = jnp.clip(jnp.searchsorted(keys, key), 0, keys.shape[0] - 1)
            return jnp.where(keys[j] == key, nxt[j], dflt[g]).astype(
                jnp.int32
            )

        _g_next = jax.vmap(_g_next1, in_axes=(None, 0, 0))
        self._g_next_host = _g_next1  # pod-free debug/testing surface

        @jax.named_scope(SCOPE_SAMPLER)
        def _g_walk_greedy(gtab, gs, logits, full):
            """Per-position masked greedy + grammar state walk over a
            spec verify window: g_t applies to ``logits[:, t]`` and
            advances by the FED token ``full[:, t+1]`` (teacher-forced;
            along the accepted prefix fed == emitted so the walk is
            exact, past the first mismatch the states are junk nothing
            consumes). ONE implementation shared by the sync and
            in-chain verify cores, so the acceptance rule cannot drift
            between them. Returns (masked greedy [n, K], states [n, K])."""
            rows = jnp.moveaxis(logits, 1, 0)  # [K, n, V]
            fed_next = jnp.concatenate(
                [full[:, 1:], jnp.zeros_like(full[:, :1])], axis=1
            ).T  # [K, n]; last row junk (no t+1)

            def _walk(g, xs):
                row_t, fed_t = xs
                mg = jnp.argmax(
                    _g_mask_rows(gtab, g, row_t), axis=-1
                ).astype(jnp.int32)
                return _g_next(gtab, g, fed_t), (mg, g)

            _, (mgreedy, gstates) = jax.lax.scan(
                _walk, gs, (rows, fed_next)
            )
            return mgreedy.T, gstates.T

        # groups a step's rows are sampled in (1: all lanes at once)
        self.sampler_groups = n_lanes // sampler_group(n_lanes, cfg.vocab_size)
        self._sample_one = jax.jit(
            lambda row, temp, topp, seed, pos: _sample_lane(
                row, temp, topp, seed, pos, jnp.argmax(row).astype(jnp.int32)
            )
        )

        @jax.named_scope(SCOPE_SAMPLER)
        def _sample_lanes_or_greedy(step, temps, topps, seeds, positions,
                                    greedy):
            # the full-vocab sampler is only worth paying when some lane
            # actually samples: an XLA Conditional (ONE branch executes at
            # runtime, unlike a select) skips the whole sampler for
            # all-greedy batches — the common serving case — with a single
            # compiled program, so no program-selection flag has to ride
            # the pod control packets
            return jax.lax.cond(
                jnp.any(temps > 0.0),
                lambda: sample_lanes(
                    step, temps, topps, seeds, positions, greedy
                ),
                lambda: greedy,
            )

        @jax.named_scope(SCOPE_SAMPLER)
        def _masked_greedy(gtab, gs, step):
            # grammar mask BEFORE both the argmax and the exact top-p search:
            # constrained lanes' greedy continuation IS the masked argmax.
            # FREE lanes (gs == 0) see an all-ones mask — identity.
            mstep = _g_mask_rows(gtab, gs, step)
            return mstep, jnp.argmax(mstep, axis=-1).astype(jnp.int32)

        def _decode_core(params, cache, tokens, positions, temps, topps,
                         seeds, gtab, gs):
            # tokens/positions: [n_lanes] -> [n_lanes, 1]
            logits, cache, counts = forward_c(
                cfg, params, tokens[:, None], positions[:, None], cache,
                emulate_q80_activations=q80, mesh=sp_mesh, q80_sync=q80s,
            )
            with jax.named_scope(SCOPE_HEAD):
                step = logits[:, 0, :]
            mstep, greedy = _masked_greedy(gtab, gs, step)
            # sampling fused into the compiled step: a sampled lane costs a
            # 4-byte token transfer, not a [vocab] f32 row read by the host
            sampled = _sample_lanes_or_greedy(
                mstep, temps, topps, seeds, positions, greedy
            )
            # the automaton advances on the CHOSEN token, on device — the
            # grammar twin of the position carry
            with jax.named_scope(SCOPE_CARRY):
                chosen = jnp.where(temps == 0.0, greedy, sampled)
                new_g = _g_next(gtab, gs, chosen)
            return step, greedy, sampled, new_g, cache, counts

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program()
        def _decode(params, cache, tokens, positions, temps, topps, seeds,
                    gtab, gs):
            with jax.named_scope(names.HALF_DECODE):
                step, greedy, sampled, _, cache, _ = _decode_core(
                    params, cache, tokens, positions, temps, topps, seeds,
                    gtab, gs,
                )
            # greedy+sampled stacked into ONE [2, n] array: a decode step
            # costs a single device->host round trip, not two (the transfer
            # is latency-bound — 8 bytes/lane payload)
            with jax.named_scope(SCOPE_HEAD):
                step = replicate(step)
            with jax.named_scope(SCOPE_CARRY):
                pair = rep_tokens(jnp.stack([greedy, sampled]))
            return step, pair, cache

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program()
        def _decode_nologits(params, cache, tokens, positions, temps, topps,
                             seeds, gtab, gs):
            # the common all-device-sampling step: no [n, vocab] output kept
            # alive (the row is still computed for argmax, but never
            # materialized as a program output, so it pins no HBM and — in
            # the pipelined path — can never force a sync)
            with jax.named_scope(names.HALF_DECODE):
                _, greedy, sampled, _, cache, _ = _decode_core(
                    params, cache, tokens, positions, temps, topps, seeds,
                    gtab, gs,
                )
            with jax.named_scope(SCOPE_CARRY):
                return rep_tokens(jnp.stack([greedy, sampled])), cache

        @jax.named_scope(SCOPE_CARRY)
        def _eff_positions(carry_pos, pos_host):
            # the carried-position select: host positions >= 0 override
            # (parked / admitting / reseeded lanes), -1 reads the device
            # carry — the only layer that knows a lane's position once a
            # spec verify step with a per-lane accept count is in flight
            return jnp.where(pos_host < 0, carry_pos, pos_host)

        # the grammar-state select is the identical rule (-1 = carry)
        _eff_g = _eff_positions

        @jax.named_scope(names.HALF_DECODE)
        def _decode_half(params, cache, feed, carry_pos, positions, temps,
                         topps, seeds, gtab, carry_g, gs_host):
            # the decode batch's half of a pipelined step, alone
            # (_decode_pl) or beside an admitted chunk (_decode_prefill):
            # the effective positions and grammar states, then one token a
            # lane. What follows it in either program (the carry and the
            # packed readback) joins the halves and sits under neither.
            pos = _eff_positions(carry_pos, positions)
            gs = _eff_g(carry_g, gs_host)
            return pos, _decode_core(
                params, cache, feed, pos, temps, topps, seeds, gtab, gs
            )

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program()
        def _decode_pl(params, cache, tokens, carry_pos, positions, temps,
                       topps, seeds, gtab, carry_g, gs_host):
            # pipelined step: the per-lane feed rule (greedy lanes continue
            # with argmax, device-sampled lanes with the fused sample — the
            # same select the decode_multi scan body applies) runs ON DEVICE
            # and comes back as the carry for the NEXT dispatch, so step k+1
            # needs no host readback of step k at all. Positions ride the
            # carry too (clamped at seq_len, where the KV scatter drops
            # writes — the same park rule the host applies); the grammar
            # state rides it identically.
            pos, (_, greedy, sampled, new_g, cache, counts) = _decode_half(
                params, cache, tokens, carry_pos, positions, temps, topps,
                seeds, gtab, carry_g, gs_host,
            )
            with jax.named_scope(SCOPE_CARRY):
                nxt = jnp.where(temps == 0.0, greedy, sampled)
                new_pos = jnp.minimum(pos + 1, cfg.seq_len)
                return (
                    rep_tokens(nxt),
                    rep_tokens(new_pos),
                    rep_tokens(new_g),
                    rep_tokens(_token_rows(greedy, sampled, counts)),
                    cache,
                )

        @jax.named_scope(SCOPE_CARRY)
        def _spec_accepted(full, greedy, draft_len):
            # (accepted, emitted) counts per lane: the longest prefix of
            # the drafts the model's own (masked) greedy path reproduces,
            # capped at the lane's draft length, and that plus the model's
            # own continuation
            match = (full[:, 1:] == greedy[:, :-1]).astype(jnp.int32)
            lead = jnp.cumprod(match, axis=1)  # leading-match indicator
            in_draft = (
                jnp.arange(full.shape[1] - 1, dtype=jnp.int32)[None, :]
                < draft_len[:, None]
            )
            accepted = jnp.sum(lead * in_draft, axis=1).astype(jnp.int32)
            return accepted, accepted + 1

        def _spec_verify_core(params, cache, feed, pos, drafts, draft_len,
                              temps, topps, seeds, gtab, gs):
            """Speculative verify INSIDE the pipelined step family: up to
            SPEC_DRAFT host-shipped draft tokens are verified against the
            device's own carry in one forward, per-lane accepted counts
            advance the position carry (pos + accepted + 1), and the next
            feed token is the model's continuation after the accepted
            prefix — exactly ``_decode_spec``'s math with one extra gate:

            drafts[:, 0] is the HOST'S CANDIDATE FOR THE CARRY TOKEN
            ITSELF. The host probes its n-gram index one step behind the
            device (its history ends at the token fed into the in-flight
            step), so it ships K+1 candidates starting at the token it
            cannot see; the device admits the remaining K only when
            candidate 0 equals the actual carry (on a reseed the host
            knows the feed exactly and ships it as candidate 0, so the
            gate passes trivially). A mismatch costs nothing but the
            acceptance — verification is against the model's own argmax,
            so emitted tokens are ALWAYS the plain greedy stream.

            Junk-KV safety is ``_decode_spec``'s contract verbatim, with
            the draft clamp moved ON DEVICE (the host's stale position
            could under-clamp): eff_len <= seq_len - pos - 1, and writes
            at >= seq_len drop in the cache scatter.

            Grammar: the automaton state WALKS the verify window — the
            state for window position t is ``gs`` advanced by the fed
            tokens ``full[1..t]``, so each position's greedy is the
            MASKED argmax under its own state (a constrained lane's
            "model's own greedy path" is the masked one; FREE lanes see
            identity masks). Along the accepted prefix the fed tokens
            equal the masked greedy, so the walk is exact; past the first
            mismatch the states are junk that nothing consumes. The new
            carry is the state after the accepted prefix plus the model's
            own continuation token."""
            with jax.named_scope(SCOPE_CARRY):
                hit0 = (drafts[:, 0] == feed) & (draft_len > 0)
                eff_len = jnp.where(hit0, draft_len - 1, 0)
                eff_len = jnp.clip(
                    eff_len, 0, jnp.maximum(cfg.seq_len - pos - 1, 0)
                )
                full = jnp.concatenate([feed[:, None], drafts[:, 1:]], axis=1)
                k_spec = full.shape[1]  # SPEC_DRAFT + 1
                pos2d = pos[:, None] + jnp.arange(k_spec, dtype=jnp.int32)
            logits, cache = forward(
                cfg, params, full, pos2d, cache,
                emulate_q80_activations=q80, mesh=sp_mesh, q80_sync=q80s,
            )

            # per-position masked greedy + state walk (K is tiny: a short
            # scan, not a flush-worthy cost) — the shared verify-window
            # rule, so sync and in-chain acceptance cannot drift
            greedy, gstates = _g_walk_greedy(gtab, gs, logits, full)

            accepted, n_emit = _spec_accepted(full, greedy, eff_len)
            with jax.named_scope(SCOPE_SAMPLER):
                mrow0 = _g_mask_rows(gtab, gs, logits[:, 0, :])
            sampled0 = _sample_lanes_or_greedy(
                mrow0, temps, topps, seeds, pos, greedy[:, 0],
            )
            with jax.named_scope(SCOPE_CARRY):
                emitted = greedy.at[:, 0].set(
                    jnp.where(temps > 0.0, sampled0, greedy[:, 0])
                )
                nxt = jnp.take_along_axis(
                    emitted, (n_emit - 1)[:, None], axis=1
                )[:, 0]
                # grammar carry: state after full[0..accepted] (the walk's
                # entry at index `accepted`), advanced by the continuation
                g_a = jnp.take_along_axis(
                    gstates, accepted[:, None], axis=1
                )[:, 0]
                new_g = _g_next(gtab, g_a, nxt)
                new_pos = jnp.minimum(pos + n_emit, cfg.seq_len)
                # ONE [n, K+2] lagged transfer: emitted tokens + emit count
                packed = jnp.concatenate([emitted, n_emit[:, None]], axis=1)
            return nxt, new_pos, new_g, packed, cache

        @jax.named_scope(names.HALF_DECODE)
        def _spec_half(params, cache, feed, carry_pos, positions, drafts,
                       draft_len, temps, topps, seeds, gtab, carry_g, gs_host):
            # _decode_half's twin for the verify steps: the decode batch's
            # half is the verify window
            pos = _eff_positions(carry_pos, positions)
            gs = _eff_g(carry_g, gs_host)
            return _spec_verify_core(
                params, cache, feed, pos, drafts, draft_len, temps, topps,
                seeds, gtab, gs,
            )

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program()
        def _decode_spec_pl(params, cache, tokens, carry_pos, positions,
                            drafts, draft_len, temps, topps, seeds, gtab,
                            carry_g, gs_host):
            nxt, new_pos, new_g, packed, cache = _spec_half(
                params, cache, tokens, carry_pos, positions, drafts,
                draft_len, temps, topps, seeds, gtab, carry_g, gs_host,
            )
            with jax.named_scope(SCOPE_CARRY):
                return (
                    rep_tokens(nxt),
                    rep_tokens(new_pos),
                    rep_tokens(new_g),
                    rep_tokens(packed),
                    cache,
                )

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program("p_tokens")
        def _decode_spec_prefill(params, cache, tokens, carry_pos,
                                 positions, drafts, draft_len, temps, topps,
                                 seeds, p_lane, p_tokens, p_start, p_n,
                                 p_temp, p_topp, p_seed, gtab, carry_g,
                                 gs_host, p_g):
            """Fused admission + speculative verify: ONE dispatch that
            consumes one bounded prompt chunk for lane ``p_lane`` AND
            verifies every generating lane's drafts — the composition the
            zero-flush chain needs when a request is admitting while
            greedy lanes draft. The prefill half is ``_prefill_half``
            verbatim (the ``decode_prefill_fused`` contract); the verify
            half is ``_spec_verify_core``; the packed readback appends the
            chunk's boundary greedy/sampled pair as one extra ROW
            ([n+1, K+2] — spec packs are row-per-lane, unlike the
            [2, n+1] column pack of the plain fused step)."""
            _, p_greedy, p_sampled, cache, _ = _prefill_half(
                params, cache, p_lane, p_tokens, p_start, p_n,
                p_temp, p_topp, p_seed, gtab, p_g,
            )
            nxt, new_pos, new_g, packed, cache = _spec_half(
                params, cache, tokens, carry_pos, positions, drafts,
                draft_len, temps, topps, seeds, gtab, carry_g, gs_host,
            )
            with jax.named_scope(SCOPE_CARRY):
                p_first = jnp.where(p_temp == 0.0, p_greedy, p_sampled)
                nxt = nxt.at[p_lane].set(p_first)
                new_pos = new_pos.at[p_lane].set(p_start + p_n)
                # the admitting lane's grammar carry: its automaton start
                # state advanced by the boundary token (junk mid-prompt, the
                # final chunk's dispatch overwrites it — the token-carry rule)
                new_g = new_g.at[p_lane].set(_g_next1(gtab, p_g, p_first))
                brow = jnp.zeros((1, packed.shape[1]), jnp.int32)
                brow = brow.at[0, 0].set(p_greedy).at[0, 1].set(p_sampled)
                packed = jnp.concatenate([packed, brow], axis=0)
                return (
                    rep_tokens(nxt),
                    rep_tokens(new_pos),
                    rep_tokens(new_g),
                    rep_tokens(packed),
                    cache,
                )

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program()
        @jax.named_scope(names.HALF_DECODE)
        def _decode_spec(params, cache, tokens, drafts, draft_len, positions,
                         temps, topps, seeds, gtab, gs):
            """Speculative decode: verify K = 1 + n_draft tokens per lane in
            ONE forward (prompt-lookup speculation — decode is weight-read-
            bound, so a K-token step costs the same HBM traffic as a 1-token
            step and emits up to K tokens on greedy lanes when drafts hit).

            tokens [n]: each lane's real next token. drafts [n, K-1]: draft
            continuations (garbage beyond draft_len). draft_len [n]: 0 for
            sampled/undrafted lanes. Emits greedy[t] for the longest prefix
            where draft[t+1] == greedy[t], plus the model's own continuation
            — exactly the tokens plain greedy decode would produce, in the
            same order (standard speculative-verification identity).

            Cache safety: all K positions get KV writes; slots past the
            accepted prefix stay uncommitted (per-lane pos only advances by
            what the scheduler consumes) and are rewritten before any query
            can read them — the same invariant chunked prefill relies on.
            Writes at positions >= seq_len are dropped by the cache scatter
            (mode="drop"), so lanes near the end of their sequence are safe
            as long as the caller clamps that lane's draft_len to
            seq_len - pos - 1 (emitted token t reads logits at pos + t,
            which needs in-bounds KV through pos + t)."""
            with jax.named_scope(SCOPE_CARRY):
                full = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [n, K]
                k_spec = full.shape[1]
                pos2d = positions[:, None] + jnp.arange(k_spec, dtype=jnp.int32)
            logits, cache = forward(
                cfg, params, full, pos2d, cache,
                emulate_q80_activations=q80, mesh=sp_mesh, q80_sync=q80s,
            )
            # per-position masked greedy via the SHARED grammar state
            # walk (the _spec_verify_core rule; identity for FREE lanes)
            greedy, _ = _g_walk_greedy(gtab, gs, logits, full)
            _, n_emit = _spec_accepted(full, greedy, draft_len)  # [n]
            # lane 0-position sample for temp>0 lanes (their draft_len is 0)
            with jax.named_scope(SCOPE_SAMPLER):
                mrow0 = _g_mask_rows(gtab, gs, logits[:, 0, :])
            sampled0 = _sample_lanes_or_greedy(
                mrow0, temps, topps, seeds, positions, greedy[:, 0],
            )
            with jax.named_scope(SCOPE_CARRY):
                emitted = greedy.at[:, 0].set(
                    jnp.where(temps > 0.0, sampled0, greedy[:, 0])
                )
                # ONE [n, K+1] transfer: emitted tokens + emit count
                packed_out = rep_tokens(
                    jnp.concatenate([emitted, n_emit[:, None]], axis=1)
                )
            with jax.named_scope(SCOPE_HEAD):
                row0 = replicate(logits[:, 0, :])
            return row0, packed_out, cache

        self._decode_spec_fn = _decode_spec

        # the most rows of logits a forward has handed a prefill half, of the
        # programs traced so far (0: none traced; path_facts)
        self._prefill_head_rows = 0

        @jax.named_scope(names.HALF_PREFILL)
        def _prefill_half(params, cache, lane, tokens, start_pos, n_tokens,
                          temp, topp, seed, gtab, p_g):
            """The prompt-chunk math shared by ``_prefill`` and the fused
            ``_decode_prefill``: lane slice, forward, KV splice, boundary
            argmax + fused sample. ONE implementation, so the fused
            admission path's byte-identical-to-prefill_chunk contract
            holds structurally, not by parallel maintenance.

            tokens: [bucket] int32, first n_tokens real; lane, start_pos,
            n_tokens traced scalars (one compile per bucket size only).
            Padded tail tokens write at positions >= start_pos + n_tokens,
            which later real writes overwrite before they become readable
            (mask s <= pos), so no masking is needed. First-token sampling
            is compiled into the step: multi-host pods replay the
            identical program (a root-only jit over the global-mesh logits
            would not be dispatchable).

            The one row of logits a chunk keeps is its last real token's:
            the forward is told so (``head_row``) and runs its head, the
            final norm and ``wcls``, over that row of the hidden state
            alone, not over the bucket (``path_facts``:
            ``prefill_head_rows``)."""
            bucket = tokens.shape[0]
            with jax.named_scope(SCOPE_CARRY):
                positions = start_pos + jnp.arange(bucket, dtype=jnp.int32)
                head_row = (n_tokens - 1)[None]
            if isinstance(cache, PagedKVCache):
                # paged layout: there is no per-lane plane to slice — the
                # POOL rides whole and the lane's one-ROW page table scopes
                # every write and read to that lane's pages (writes beyond
                # its mapped blocks hit sentinel entries and drop)
                with jax.named_scope(SCOPE_CARRY):
                    row = jax.lax.dynamic_slice_in_dim(cache.table, lane, 1, axis=0)
                logits, lane_cache, counts = forward_n(
                    cfg,
                    params,
                    tokens[None, :],
                    positions[None, :],
                    PagedKVCache(k=cache.k, v=cache.v, table=row),
                    head_row=head_row,
                    emulate_q80_activations=q80,
                    mesh=sp_mesh,
                    q80_sync=q80s,
                )
                out_cache = PagedKVCache(
                    k=lane_cache.k, v=lane_cache.v, table=cache.table
                )
            else:
                # slice this lane's cache to batch-of-1 (the splice of an
                # admitted lane: out here, and back in below). Every leaf of
                # a contiguous cache has its lanes on axis 1: K and V, the
                # latent block's two leaves, a layer pattern's conv state
                with jax.named_scope(SCOPE_CARRY):
                    lane_in = jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_slice_in_dim(a, lane, 1, axis=1), cache)
                logits, lane_cache, counts = forward_n(
                    cfg,
                    params,
                    tokens[None, :],
                    positions[None, :],
                    lane_in,
                    # the chunk's real tokens: a state that is overwritten in
                    # place must not absorb the bucket's padded tail
                    n_valid=n_tokens[None],
                    head_row=head_row,
                    emulate_q80_activations=q80,
                    mesh=sp_mesh,
                    q80_sync=q80s,
                )
                with jax.named_scope(SCOPE_CARRY):
                    out_cache = jax.tree_util.tree_map(
                        lambda a, b: jax.lax.dynamic_update_slice_in_dim(a, b, lane, axis=1),
                        cache, lane_cache)
            # trace-time witness: the rows of logits the forward handed back
            self._prefill_head_rows = max(self._prefill_head_rows, logits.shape[1])
            with jax.named_scope(SCOPE_HEAD):
                last = logits[0, 0]
            # grammar: the boundary token — the request's FIRST generated
            # token when this is the final chunk — samples under the
            # automaton's start-state mask (p_g; 0 = FREE = identity)
            with jax.named_scope(SCOPE_SAMPLER):
                mlast = _g_mask_row(gtab, p_g, last)
                greedy = jnp.argmax(mlast).astype(jnp.int32)
                # same runtime gate as the decode families: a greedy admission
                # (temp 0) skips the full-vocab sampler entirely
                sampled = jax.lax.cond(
                    temp > 0.0,
                    lambda: _sample_lane(
                        mlast, temp, topp, seed, start_pos + n_tokens - 1, greedy
                    ),
                    lambda: greedy,
                )
            return last, greedy, sampled, out_cache, counts

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program("tokens")
        def _prefill(params, cache, lane, tokens, start_pos, n_tokens,
                     temp, topp, seed, gtab, p_g):
            last, greedy, sampled, cache, _ = _prefill_half(
                params, cache, lane, tokens, start_pos, n_tokens,
                temp, topp, seed, gtab, p_g,
            )
            with jax.named_scope(SCOPE_HEAD):
                last = replicate(last)
            with jax.named_scope(SCOPE_CARRY):
                pair = rep_tokens(jnp.stack([greedy, sampled]))
            return last, pair, cache

        @partial(jax.jit, donate_argnums=(1,))
        @_step_program("p_tokens")
        def _decode_prefill(params, cache, feed, carry_pos, positions,
                            temps, topps, seeds, p_lane, p_tokens, p_start,
                            p_n, p_temp, p_topp, p_seed, gtab, carry_g,
                            gs_host, p_g):
            """Fused prefill+decode: ONE device dispatch that consumes one
            bucketed prompt chunk for lane ``p_lane`` AND advances every
            generating lane one pipelined decode step — the stall-free
            admission unit. Compiles once per prefill bucket (p_tokens
            shape), like ``_prefill``.

            The prefill half IS ``_prefill``'s math — the shared
            ``_prefill_half`` closure (lane slice, padded-tail
            overwrite-before-readable, boundary-token sampling fused in);
            the decode half is byte-identical math to
            ``_decode_pl`` (same feed rule, same fold_in(seed, pos) draws)
            — lanes are a batch axis, so the admitting lane's fresh KV is
            invisible to the generating lanes' attention and their token
            streams equal the unfused path's exactly. The admitting lane
            rides the decode batch too, parked at position seq_len (its
            junk write drops, its junk sample is overwritten below).

            Carry: the admitting lane's slot holds the chunk's boundary
            token (greedy at temp 0, fused-sampled otherwise — exactly the
            first generated token when this is the FINAL chunk), so the
            next dispatch can feed a freshly admitted lane without any
            host round-trip; mid-prompt that slot is junk the same way an
            idle lane's is. Output is ONE [2, n+1] pack: decode greedy/
            sampled rows plus the prefill boundary pair in the extra
            column."""
            _, p_greedy, p_sampled, cache, chunk_counts = _prefill_half(
                params, cache, p_lane, p_tokens, p_start, p_n,
                p_temp, p_topp, p_seed, gtab, p_g,
            )
            pos, (_, greedy, sampled, new_g, cache, counts) = _decode_half(
                params, cache, feed, carry_pos, positions, temps, topps,
                seeds, gtab, carry_g, gs_host,
            )
            with jax.named_scope(SCOPE_CARRY):
                nxt = jnp.where(temps == 0.0, greedy, sampled)
                # host-exact admissions never take the fused path, so the
                # boundary feed rule is the plain temp-0-greedy-else-sampled
                # select the sync _prefill_step applies
                p_first = jnp.where(p_temp == 0.0, p_greedy, p_sampled)
                nxt = nxt.at[p_lane].set(p_first)
                # the joined lane's NEXT write position is the chunk boundary:
                # carried on device so the lane can ride spec steps immediately
                new_pos = jnp.minimum(pos + 1, cfg.seq_len)
                new_pos = new_pos.at[p_lane].set(p_start + p_n)
                # its grammar carry joins the same way: start state advanced
                # by the boundary token (junk mid-prompt; final chunk wins)
                new_g = new_g.at[p_lane].set(_g_next1(gtab, p_g, p_first))
                # the boundary column brings the chunk's tiles (TILE_COUNTS);
                # every other count is the decode steps' alone
                p_counts = counts and tuple(
                    c if name in TILE_COUNTS else jnp.zeros_like(c)
                    for name, c in zip(self._count_names, chunk_counts))
                packed = jnp.concatenate(
                    [
                        _token_rows(greedy, sampled, counts),
                        _token_rows(p_greedy, p_sampled, p_counts)[:, None],
                    ],
                    axis=1,
                )
                return (
                    rep_tokens(nxt),
                    rep_tokens(new_pos),
                    rep_tokens(new_g),
                    rep_tokens(packed),
                    cache,
                )

        @partial(jax.jit, donate_argnums=(0,))
        def _copy_lane(cache, src, dst):
            # whole-lane KV copy (prefix caching): static shapes mean ONE
            # compile for any prefix length; slots past the shared prefix
            # are garbage for dst, but dst's prefill rewrites them before
            # any query can read them (the chunked-prefill invariant). The
            # copy is an HBM-to-HBM move (~cache-lane bytes), orders of
            # magnitude cheaper than re-prefilling the prefix.
            return jax.tree_util.tree_map(
                lambda a: a.at[:, dst].set(
                    jax.lax.dynamic_index_in_dim(a, src, axis=1, keepdims=False)),
                cache,
            )

        @partial(jax.jit, donate_argnums=(0,))
        def _copy_page(cache, src, dst):
            # single-page HBM copy — the paged path's copy-on-write unit
            # (page_size tokens x all layers, vs _copy_lane's whole-lane
            # move): traced scalars mean ONE compile for any (src, dst)
            # pair. Slots past the divergence point carry the source's
            # stale content, which the tail prefill rewrites before any
            # query can read them (the chunked-prefill invariant).
            k_src = jax.lax.dynamic_index_in_dim(cache.k, src, axis=1, keepdims=False)
            v_src = jax.lax.dynamic_index_in_dim(cache.v, src, axis=1, keepdims=False)
            return PagedKVCache(
                k=cache.k.at[:, dst].set(k_src),
                v=cache.v.at[:, dst].set(v_src),
                table=cache.table,
            )

        self._copy_page_fn = _copy_page

        @partial(jax.jit, donate_argnums=(0,))
        def _write_page(cache, page, k_page, v_page):
            # whole-page K/V write — the disagg IMPORT unit (a page
            # arriving from a peer replica lands here). Traced page
            # scalar + fixed host-array operand avals: ONE compile for
            # any destination page, warmed at warmup like the COW copy.
            return PagedKVCache(
                k=cache.k.at[:, page].set(k_page),
                v=cache.v.at[:, page].set(v_page),
                table=cache.table,
            )

        self._write_page_fn = _write_page

        @jax.jit
        def _gather_pages(cache, idx):
            # batched page READ for host swap-out: NOT donated — the
            # cache stays the live serving pytree, and dispatch order
            # (this read before any later-dispatched donated write)
            # guarantees the gathered bytes are the pre-eviction content
            # even when the pages are already re-popped for the same
            # admission. Fixed [_SWAP_BATCH] idx operand: ONE compile.
            return cache.k[:, idx], cache.v[:, idx]

        self._gather_pages_fn = _gather_pages

        @partial(jax.jit, donate_argnums=(0,))
        def _scatter_pages(cache, idx, k_pages, v_pages):
            # batched page WRITE for host swap-in — the donated cache
            # pytree orders it before any later-dispatched tail
            # prefill/decode, exactly like a COW copy, and the fixed
            # [_SWAP_BATCH] operand shapes mean ONE compile for any
            # destination set (padding repeats a real page with its own
            # content — an idempotent duplicate write)
            return PagedKVCache(
                k=cache.k.at[:, idx].set(k_pages),
                v=cache.v.at[:, idx].set(v_pages),
                table=cache.table,
            )

        self._scatter_pages_fn = _scatter_pages

        def _make_decode_multi(h):
            @partial(jax.jit, donate_argnums=(1,))
            @_step_program(width=h)
            @jax.named_scope(names.HALF_DECODE)
            def _decode_multi(params, cache, tokens, positions, temps, topps,
                              seeds, gtab, gs):
                """h chained decode steps in ONE device program (lax.scan):
                greedy lanes feed argmax forward, device-sampled lanes feed
                their fused sample (same fold_in(seed, pos) stream as h
                single steps — the token sequences are identical). One
                [h, n] transfer replaces h round trips; through a
                high-latency device link (the serving loop's regime) the
                per-token dispatch overhead drops by h. Host-side EOS/stop
                handling is retroactive: steps past a lane's stop write
                junk KV that the overwrite-before-readable invariant
                (chunked prefill, spec verify) already covers. The grammar
                state threads the scan carry like the position does."""
                def body(carry, _):
                    tok, pos, g, cache = carry
                    logits, cache = forward(
                        cfg, params, tok[:, None], pos[:, None], cache,
                        emulate_q80_activations=q80, mesh=sp_mesh,
                        q80_sync=q80s,
                    )
                    with jax.named_scope(SCOPE_HEAD):
                        step = logits[:, 0, :]
                    mstep, greedy = _masked_greedy(gtab, g, step)
                    sampled = _sample_lanes_or_greedy(
                        mstep, temps, topps, seeds, pos, greedy
                    )
                    with jax.named_scope(SCOPE_CARRY):
                        nxt = jnp.where(temps == 0.0, greedy, sampled)
                        return (nxt, pos + 1, _g_next(gtab, g, nxt), cache), nxt

                (_, _, _, cache), chosen = jax.lax.scan(
                    body, (tokens, positions, gs, cache), None, length=h
                )
                with jax.named_scope(SCOPE_CARRY):
                    return rep_tokens(chosen), cache  # chosen [h, n]

            return _decode_multi

        self._make_decode_multi = _make_decode_multi
        self._decode_multi_fns: dict[int, object] = {}

        self._copy_lane_fn = _copy_lane
        self._decode_prefill_fn = _decode_prefill
        self._decode_fn = _decode
        self._decode_nologits_fn = _decode_nologits
        self._decode_pl_fn = _decode_pl
        self._decode_spec_pl_fn = _decode_spec_pl
        self._decode_spec_prefill_fn = _decode_spec_prefill
        self._prefill_fn = _prefill
        # AOT-compiled decode executable (set by collective_stats, which
        # must lower+compile to read the post-SPMD HLO): reused for dispatch
        # so --benchmark mesh runs don't compile the decode step twice
        self._decode_exec = None

    # -- grammar-constrained decoding (grammar/) ----------------------------

    def _prefill_attn_blocks(self, start: int, n_rows: int, bucket: int) -> tuple[int, int]:
        """(visited, causal) of one prompt chunk, in (query row, key block)
        pairs over the layers whose attention is computed a key block at a
        time (ops/blocked_attention.py): host integers, for the two
        prefill_attn_blocks_* counters. (0, 0) where the chunk's scores are
        dense, and for a block that has no such path."""
        cfg = self.config
        if not cfg.layer_kinds or self.kvpool is not None:
            return 0, 0
        visited = causal = 0
        for layers, rows, window in (
            (cfg.n_attention_layers, cfg.seq_len, 0),
            (cfg.n_window_layers, self.ring_rows, cfg.sliding_window),
        ):
            if layers and blocked_attention.engages(1, bucket, cfg.n_heads, rows):
                v, c = blocked_attention.chunk_block_counts(start, n_rows, bucket, rows, window)
                visited, causal = visited + layers * v, causal + layers * c
        return visited, causal

    def path_facts(self) -> dict:
        """Which attention and which expert path this engine's decode steps
        run and how its prefill chunks read the cache, by the predicates the
        forward itself asks, and what the kernel bodies traced so far are
        (``q40_weight_passes``, ``q40_offset_subtracted``,
        ``q40_scales_in_stack``, ``q40_scale_converts``,
        ``prefill_kernel_traces``: the trace-time witnesses of
        ``ops/pallas_q40.py`` and ``ops/pallas_attention.py``;
        ``prefill_head_rows``: this engine's own, of ``_prefill_half``): said
        once at start-up (the ``runtime_device`` line, ``/stats``), so that a
        fallback is never silent."""
        from ..ops.linear import pallas_kernel_active
        from ..ops.pallas_q40 import TRACE_STATS as q40_trace_stats
        from ..ops.pallas_q40_grouped import grouped_supports

        cfg = self.config
        # how a step reads the cache where no kernel reads it in place
        if cfg.sparse_attention:
            planes = "sparse_topk"
        elif cfg.latent_attention:
            planes = "xla_dense_latent_absorbed"
        else:
            planes = "xla_dense"
        attention = "pallas_in_place" if self.decode_attention_block is not None else planes
        if cfg.n_experts == 0:
            experts = None
        elif not cfg.n_routed_layers:
            experts = "mixtral_moe_ffn"
        elif grouped_supports(self.params.routed.w1) and pallas_kernel_active():
            experts = "q40_grouped_kernel"
        else:
            experts = "xla_gathered_slabs"
        # how a prefill chunk's rows (one lane, the largest bucket's) read a
        # plain full-context plane: the key blocks the chunk can see, in
        # place on the chip; the same walk as an XLA loop (a layer-pattern
        # block's long planes); or dense scores over the whole plane. A latent
        # cache has no such plane, and its chunks keep the absorbed form over
        # the latent plane (or the indexer's rows): that path's name
        chunk = self.prefill_buckets[-1]
        if cfg.latent_attention:
            prefill = planes
        elif prefill_attention_engages(
                self.cache, self.mesh, 1, chunk, cfg.n_heads, cfg.n_kv_heads):
            prefill = "in_place_kernel"
        elif cfg.layer_kinds and blocked_attention.engages(1, chunk, cfg.n_heads, cfg.seq_len):
            prefill = "blocked"
        else:
            prefill = "dense"
        facts = {"attention_path": attention, "prefill_attention_path": prefill,
                 # prefill kernel bodies traced so far (after warm-up: above 0
                 # wherever the path above says in_place_kernel; 0 there: the
                 # chunks still form dense scores)
                 "prefill_kernel_traces": pallas_attention.TRACE_STATS["prefill_kernel_traces"],
                 # rows of a chunk that meet the final norm and wcls: 1 once a
                 # prefill program is traced and the forward cut the hidden
                 # state to the row the chunk keeps; the largest bucket before
                 # that, and after it if a forward still heads every row
                 "prefill_head_rows": self._prefill_head_rows or chunk,
                 "expert_path": experts,
                 "sampler_groups": self.sampler_groups,
                 # the most passes over its weight plane any Q40 kernel call
                 # traced so far makes (after warm-up: 1 where every prefill
                 # bucket's rows share one dequantised slab; 0: none traced)
                 "q40_weight_passes": q40_trace_stats["weight_passes_max"],
                 # Q40 kernel bodies traced so far that take the nibbles' -8
                 # off in the dequant chain and hold no correction dot (after
                 # warm-up: above 0 wherever a prefill bucket or the lanes
                 # reach ops.pallas_q40.SUBTRACT_MIN_ROWS rows; 0: every call
                 # still folds it)
                 "q40_offset_subtracted": q40_trace_stats["offset_subtracted_traces"],
                 # Q40 kernel bodies traced so far whose scale tiles were read
                 # out of the weight's own int16 plane or stack in place (after
                 # warm-up: above 0 where a stack is too large for XLA to stage
                 # whole, a 7B model's FFN stacks; 0 there: every call still
                 # slices its plane out), and those fed by a FLOAT16 scale
                 # plane sliced out and converted before the call
                 "q40_scales_in_stack": q40_trace_stats["scale_stack_reads"],
                 "q40_scale_converts": q40_trace_stats["scale_converts"]}
        if cfg.sparse_attention:
            # how the chosen rows are read (models/deepseek.py: gathered,
            # at every width), and what an indexer's rows are not computed for
            facts.update(
                index_topk=cfg.index_topk,
                sparse_rows="gathered",
                declined_for_sparse_attention=["speculation"],
            )
        if cfg.experts_held_count:
            facts["experts_held"] = f"{cfg.experts_held_count}/{cfg.n_experts}"
        if cfg.n_window_layers:
            # two kinds of cache a lane: the full-context layers' planes and
            # the window layers' rings, in bytes over all lanes
            facts.update(
                window_attention_path=(
                    "pallas_in_place_ring" if self.decode_ring_block is not None
                    else "xla_dense_ring"),
                sliding_window=cfg.sliding_window,
                kv_ring_rows=self.ring_rows,
                kv_ring_bytes=self.cache.wk.nbytes + self.cache.wv.nbytes,
                kv_plane_bytes=self.cache.k.nbytes + self.cache.v.nbytes,
            )
            if cfg.split_kv_kinds or cfg.window_sink or cfg.value_head_size != cfg.head_size:
                # heads that differ by kind: the decode path and a cached
                # position's (key, value) row widths, a kind
                facts.update(
                    attention_path_by_kind={
                        "full": "pallas_in_place" if self.decode_attention_block is not None
                        else "xla_dense",
                        "window": "pallas_in_place" if self.decode_ring_block is not None
                        else "xla_dense"},
                    kv_row_widths_by_kind={
                        "full": list(cfg.kv_widths()), "window": list(cfg.kv_widths(True))},
                    window_sink=cfg.window_sink == 1,
                )
        if cfg.n_linear_layers:
            facts.update(
                linear_attention_layers=cfg.n_linear_layers,
                linear_state_bytes=self.cache.lin.nbytes,
            )
        if cfg.n_delta_layers:
            # two leaves a lane beside the planes: the float32 matrix state
            # and the three convs' windows; and which one-row path a decode
            # step's update takes (ops/delta_rule.py)
            facts.update(
                delta_rule_layers=cfg.n_delta_layers,
                delta_state_bytes=self.cache.delta.nbytes,
                delta_conv_window_bytes=self.cache.delta_conv.nbytes,
                delta_state_path=delta_rule.one_row_path(
                    self.n_lanes, cfg.delta_n_heads, cfg.delta_head_dim),
                kv_plane_bytes=self.cache.k.nbytes + self.cache.v.nbytes,
            )
        if cfg.n_sparse_layers:
            # a third kind of cache a lane beside planes and matrix state
            facts.update(
                block_sparse_path=(
                    "pallas_chosen_blocks" if block_sparse_engages(self.cache, self.mesh, cfg)
                    else "xla_masked_key_blocks"),
                sparse_blocks=f"{cfg.sparse_topk}x{cfg.sparse_block_size}@{cfg.sparse_dense_len}",
                compressed_key_bytes=self.cache.ck.nbytes,
                kv_plane_bytes=self.cache.k.nbytes + self.cache.v.nbytes,
            )
        if self.chunk_taper_start is not None:
            facts["chunk_taper"] = f"{self.prefill_buckets[-2]}@{self.chunk_taper_start}"
        if cfg.recurrent_state:
            # what is declined for a state overwritten in place, said where
            # the paths are said
            facts.update(
                recurrent_state_bytes=self.lane_state_bytes,
                declined_for_recurrent_state=["prefix_reuse", "speculation"],
                # refused at construction, by name (__init__), for any block
                # that keeps a stack a kind of layer on one device
                refused_for_recurrent_state=[
                    "paged_kv", "kv_host_tier", "kv_page_transfer", "migration", "mesh"],
            )
        return facts

    # the scheduler gates response_format requests on this; pod roots
    # broadcast attach/detach as OP_GRAMMAR packets (RootControlEngine)
    @property
    def supports_grammar(self) -> bool:
        return self._g_vocab is not None

    def grammar_init(self, token_table, eos_ids) -> None:
        """Register the tokenizer's piece table (raw bytes per token id,
        None for special tokens) + EOS ids — what the automaton compiler
        walks. Model vocab padding beyond the tokenizer table compiles as
        illegal-everywhere. Without this call, ``response_format``
        requests are refused (the --grammar off escape hatch)."""
        from ..grammar.automaton import vocab_fingerprint

        table = list(token_table)[: self.config.vocab_size]
        table += [None] * (self.config.vocab_size - len(table))
        self._g_vocab = table
        self._g_vocab_key = vocab_fingerprint(table)
        self._g_eos = tuple(int(e) for e in eos_ids)  # dlint: ok[host-sync] eos_ids are host ints from the tokenizer, never device values

    def grammar_attach(self, rf: dict):
        """Compile ``response_format`` (cached per (vocab, schema)) and
        install it into the slab; returns the :class:`~..grammar.slab.
        SlabHandle` whose ``start_state`` the lane's grammar carry seeds
        from. Raises the ValueError family (GrammarError) on a bad
        schema — request-scoped, a 400 — and
        :class:`~..grammar.slab.GrammarSlabFull` when live schemas
        exhaust the slab (load: the scheduler sheds it retryably)."""
        if self._g_vocab is None:
            raise ValueError(
                "structured output is disabled on this engine "
                "(--grammar off, or no tokenizer vocab registered)"
            )
        from ..grammar.automaton import compile_automaton

        auto = compile_automaton(
            rf, self._g_vocab, self._g_eos, vocab_key=self._g_vocab_key
        )
        handle = self.grammar_slab.attach(auto)
        with self.stats.lock:
            self.stats.grammar_lanes += 1
        return handle

    def grammar_detach(self, key: str) -> None:
        """Release one attach reference (the tables park for the next
        same-schema admission; evicted only under slab pressure)."""
        self.grammar_slab.detach(key)

    def grammar_stats(self) -> dict:
        """Slab pressure snapshot for /stats; {} when grammar is off."""
        return (
            self.grammar_slab.stats() if self._g_vocab is not None else {}
        )

    def _gtab(self):
        """The slab's device copies, re-uploaded only when the slab
        version moved (a new schema installed / an entry evicted) —
        shapes are capacity-fixed and the leaves go through
        ``_replace_leaf``, so this is never a recompile."""
        if self._g_version != self.grammar_slab.version:
            masks, ek, en, dflt = self.grammar_slab.arrays()
            self._g_dev = tuple(
                self._replace_leaf(a, self._g_sharding)
                for a in (masks, ek, en, dflt)
            )
            self._g_version = self.grammar_slab.version
        return self._g_dev

    def _g_vec(self, g_states, reseed: bool) -> np.ndarray:
        """Default grammar-state vector: all-FREE on a reseed (there is
        no carry), all-carry (-1) on a chained dispatch — so engines
        serving no constrained lane behave exactly as before."""
        if g_states is not None:
            return g_states
        if reseed:
            return np.zeros(self.n_lanes, np.int32)
        return np.full(self.n_lanes, -1, np.int32)

    # -- public API ---------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def max_chunk(self, start: int = 0) -> int:
        """The most rows a prompt chunk whose first row stands at ``start``
        takes: the largest bucket, and the one under it from
        ``chunk_taper_start`` on (ops/blocked_attention.py ``taper_start``)."""
        if self.chunk_taper_start is not None and start >= self.chunk_taper_start:
            return self.prefill_buckets[-2]
        return self.prefill_buckets[-1]

    def prefill_chunk(
        self,
        lane: int,
        chunk: list[int],
        start_pos: int,
        temp: float = 0.0,
        topp: float = DEFAULT_TOPP,
        seed: int = 0,
        g_state: int = 0,
    ):
        """One bucketed prompt chunk for one lane — the unit the scheduler
        interleaves between decode steps so active lanes never stall more
        than one bucket of prefill. Returns (last_logits [vocab]
        device array, greedy_token int, sampled_token int — equals greedy
        at temp 0)."""
        if len(chunk) > self.max_chunk():
            raise ValueError(f"chunk of {len(chunk)} exceeds bucket {self.max_chunk()}")
        if start_pos + len(chunk) > self.config.seq_len:
            raise ValueError(
                f"chunk of {len(chunk)} tokens at pos {start_pos} exceeds "
                f"seq_len {self.config.seq_len}"
            )
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        t0 = time.perf_counter()
        bucket = self.bucket_for(len(chunk))
        padded = np.zeros(bucket, np.int32)
        padded[: len(chunk)] = chunk
        last, toks, self.cache = self._prefill_fn(
            self.params,
            self.cache,
            jnp.int32(lane),
            jnp.asarray(padded),
            jnp.int32(start_pos),
            jnp.int32(len(chunk)),
            jnp.float32(temp),
            jnp.float32(topp),
            jnp.uint32(seed & 0xFFFFFFFF),
            self._gtab(),
            jnp.int32(g_state),
        )
        # dlint: ok[host-sync] the one [2] int32 readback per prefill chunk (greedy+sampled), counted below
        toks_np = np.asarray(toks)
        greedy = int(toks_np[0])
        sampled = int(toks_np[1])
        with self.stats.lock:
            self.stats.host_bytes_in += toks_np.nbytes
            self.stats.prefill_s += time.perf_counter() - t0
            self.stats.prefill_tokens += len(chunk)
            self.stats.prefill_bucket_rows += bucket
            self.stats.state_zero_starts += int(self.config.recurrent_state and start_pos == 0)
            self.stats.ssm_rows_scanned += len(chunk) * self.config.n_ssm_layers
            self.stats.ssm_rows_computed += bucket * self.config.n_ssm_layers
            self.stats.linear_rows_computed += bucket * self.config.n_linear_layers
            self.stats.delta_rows_computed += bucket * self.config.n_delta_layers
            visited, causal = self._prefill_attn_blocks(start_pos, len(chunk), bucket)
            self.stats.prefill_attn_blocks_visited += visited
            self.stats.prefill_attn_blocks_causal += causal
        return last, greedy, sampled

    def prefill(
        self,
        lane: int,
        tokens: list[int],
        start_pos: int = 0,
        temp: float = 0.0,
        topp: float = DEFAULT_TOPP,
        seed: int = 0,
        g_state: int = 0,
    ):
        """Process a full prompt on one lane in bucketed chunks. Returns
        (last_logits np[vocab], greedy_token int, total_positions)."""
        if not tokens:
            raise ValueError("prefill needs at least one token (empty prompt)")
        pos = start_pos
        remaining = list(tokens)
        last = greedy = None
        while remaining:
            chunk = remaining[: self.max_chunk(pos)]
            remaining = remaining[len(chunk) :]
            last, greedy, self.last_sampled = self.prefill_chunk(
                lane, chunk, pos, temp=temp, topp=topp, seed=seed,
                g_state=g_state,
            )
            pos += len(chunk)
        return last, greedy, pos

    def decode(
        self,
        tokens: np.ndarray,
        positions: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        want_logits: bool = True,
        g_states: np.ndarray | None = None,
    ):
        """One decode step for all lanes. tokens/positions: int32 [n_lanes]
        (idle lanes: any in-range position; their writes are never readable).
        temps/topps/seeds (optional, [n_lanes]) drive on-device sampling.
        Returns (logits device-array [n_lanes, vocab], greedy np[n_lanes],
        sampled np[n_lanes] — equals greedy where temps == 0).

        ``want_logits=False`` (the common all-device-sampling step — no
        host-exact lane will read them) returns None logits and runs the
        no-logits-output program: the [n_lanes, vocab] f32 row is never
        materialized, so it pins no HBM between steps."""
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        t0 = time.perf_counter()
        if g_states is None:
            g_states = np.zeros(n, np.int32)
        operands = (
            self.params,
            self.cache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(topps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            self._gtab(),
            jnp.asarray(g_states, jnp.int32),
        )
        if want_logits:
            fn = self._decode_exec if self._decode_exec is not None else self._decode_fn
            logits, toks, self.cache = fn(*operands)
        else:
            logits = None
            toks, self.cache = self._decode_nologits_fn(*operands)
        # dlint: ok[host-sync] the ONE [2, n] int32 readback per decode step (greedy+sampled rows), counted below
        toks_np = np.asarray(toks)
        greedy_np, sampled_np = toks_np[0], toks_np[1]
        with self.stats.lock:
            self.stats.host_bytes_in += toks_np.nbytes
            self.stats.decode_s += time.perf_counter() - t0
            self.stats.decode_steps += 1
            self.stats.sync_bytes_total += self.stats.sync_bytes_per_decode
        return logits, greedy_np, sampled_np

    # pod roots broadcast multi-step decodes as OP_DECODE_MULTI packets
    supports_multi_step = True

    def decode_multi(
        self,
        tokens: np.ndarray,
        positions: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        h: int = 8,
        g_states: np.ndarray | None = None,
    ) -> np.ndarray:
        """``h`` chained decode steps for all lanes in one device dispatch.

        Feed rule per lane and step: greedy lanes (temp 0) continue with
        argmax, device-sampled lanes with the fused sampler — byte-identical
        to ``h`` successive ``decode`` calls (same fold_in(seed, pos) draw
        per position). Host-exact-sampling lanes are NOT supported (they
        need full logits on host every step); callers gate on that.

        Returns ``chosen`` np[h, n]: the token each lane would feed at step
        j+1. The caller consumes its current next_token plus chosen[:h-1]
        and adopts chosen[h-1] as the new next_token, discarding everything
        after a lane's stop condition — junk KV from discarded steps is
        rewritten before any query can read it (the chunked-prefill
        invariant; see _decode_multi)."""
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        if g_states is None:
            g_states = np.zeros(n, np.int32)
        fn = self._decode_multi_fns.get(h)
        if fn is None:
            fn = self._decode_multi_fns[h] = self._make_decode_multi(h)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        t0 = time.perf_counter()
        chosen, self.cache = fn(
            self.params,
            self.cache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(topps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            self._gtab(),
            jnp.asarray(g_states, jnp.int32),
        )
        # dlint: ok[host-sync] the ONE [h, n] int32 readback per multi-step dispatch, counted below
        chosen_np = np.asarray(chosen)
        with self.stats.lock:
            self.stats.host_bytes_in += chosen_np.nbytes
            self.stats.decode_s += time.perf_counter() - t0
            self.stats.decode_steps += h
            self.stats.multi_dispatches += 1
            self.stats.sync_bytes_total += h * self.stats.sync_bytes_per_decode
        return chosen_np

    # pod roots broadcast pipelined dispatches as OP_DECODE_PIPELINED packets
    supports_pipelined = True

    def pipeline_inflight(self) -> int:
        """Dispatched-but-unconsumed pipelined steps (ring occupancy)."""
        return len(self._pl_inflight)

    def pipeline_ready(self) -> bool:
        """Whether the device has finished every step in flight: the
        youngest one's packed output is ready (steps run in dispatch order,
        so the older ones are too). A poll of the array's state:
        ``jax.Array.is_ready()`` neither blocks nor transfers. False on an
        empty ring: nothing was running."""
        return len(self._pl_inflight) > 0 and self._pl_inflight[-1][1].is_ready()

    @property
    def pipeline_active(self) -> bool:
        """True while a pipelined chain holds state (in-flight steps or a
        device token carry) — direct decode/spec callers must flush first."""
        return len(self._pl_inflight) > 0 or self._pl_carry is not None

    def decode_pipelined(
        self,
        positions: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        tokens: np.ndarray | None = None,
        g_states: np.ndarray | None = None,
    ) -> None:
        """Dispatch ONE pipelined decode step and return without reading
        anything back (JAX async dispatch queues the program immediately).

        ``tokens=None`` feeds the ON-DEVICE carry — the previous step's
        per-lane where(temp==0, greedy, sampled) select, which is exactly
        the token the synchronous loop would have fed after its readback —
        so chained dispatches never round-trip tokens through the host.
        Passing a host ``tokens`` array (re)seeds the chain (the first step
        after a flush). Temps/topps/seeds are host metadata riding each
        dispatch without any sync; a position of ``-1`` selects the
        DEVICE-CARRIED position for that lane (required once a spec verify
        step — whose per-lane accept count the host learns one step late —
        is anywhere in the chain), while ``>= 0`` overrides from host
        metadata (parked/admitting lanes at seq_len, real positions on a
        reseed — a reseed must not pass -1 anywhere, there is no carry).

        The ring is bounded at ``pipeline_depth``: callers must
        ``pipeline_consume()`` the oldest step before dispatching past it.
        Junk steps dispatched after a lane's (not-yet-discovered) stop are
        safe: their KV writes land above the lane's committed tokens and are
        rewritten before any query reads them — the same discard rule
        chunked prefill and ``decode_multi`` overshoot rely on."""
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        self.check_pipelined_dispatch(tokens is not None, positions,
                                      g_states)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        g_states = self._g_vec(g_states, tokens is not None)
        feed, carry_pos, carry_g = self._pl_feed(tokens, positions)
        nxt, new_pos, new_g, packed, self.cache = self._decode_pl_fn(
            self.params,
            self.cache,
            feed,
            carry_pos,
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(topps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            self._gtab(),
            carry_g,
            jnp.asarray(g_states, jnp.int32),
        )
        self._pl_carry = nxt
        self._pl_carry_pos = new_pos
        self._pl_carry_g = new_g
        self._pl_inflight.append(("tok", packed, time.perf_counter()))
        with self.stats.lock:
            self.stats.pipeline_dispatches += 1
            self.stats.sync_bytes_total += self.stats.sync_bytes_per_decode
            d = len(self._pl_inflight)
            self.stats.pipeline_depth_hist[d] = (
                self.stats.pipeline_depth_hist.get(d, 0) + 1
            )

    # pod roots broadcast fused admission steps as OP_DECODE_PREFILL_FUSED
    supports_fused_prefill = True

    def _pl_feed(self, tokens, positions):
        """Resolve the (feed tokens, carried positions, carried grammar
        states) operand triple for a pipelined-family dispatch: the
        device carries when chained (``tokens is None``), host arrays on
        a reseed — where the carried operands are zeros placeholders the
        ``-1`` selects never read, because a reseed must pass real
        positions (and grammar states) everywhere."""
        if tokens is None:
            return self._pl_carry, self._pl_carry_pos, self._pl_carry_g
        z = jnp.zeros(self.n_lanes, jnp.int32)
        return jnp.asarray(tokens, jnp.int32), z, z

    def check_pipelined_dispatch(self, reseed: bool,
                                 positions=None,
                                 g_states=None) -> None:
        """Raise every host-side error a pipelined dispatch would, WITHOUT
        dispatching: pod roots call this before broadcasting the control
        packet so a bad call dies on the root with ZERO packets out — a
        packet whose root-side compute never happens leaves worker rings
        and carries desynced and deadlocks the next collective. The
        reseed-position rule is part of this set for the same reason: a
        ``-1`` carried-position sentinel on a reseed (there is no carry to
        read) must die BEFORE any packet, not in every process's
        ``_pl_feed`` mid-replay."""
        if reseed and positions is not None and int(np.min(positions)) < 0:
            raise ValueError(
                "reseed dispatch with a -1 position: the carried-position "
                "select has no carry to read on a reseed — pass real "
                "positions for every lane"
            )
        if reseed and g_states is not None and int(np.min(g_states)) < 0:
            raise ValueError(
                "reseed dispatch with a -1 grammar state: the carried-"
                "state select has no carry to read on a reseed — pass "
                "real states (0 = unconstrained) for every lane"
            )
        if len(self._pl_inflight) >= max(1, self.pipeline_depth):
            raise RuntimeError(
                f"pipeline ring full (depth {self.pipeline_depth}): consume "
                "the oldest in-flight step before dispatching another"
            )
        if not reseed and self._pl_carry is None:
            raise RuntimeError(
                "no device token carry: seed the chain with tokens= "
                "(first dispatch after construction or a flush)"
            )

    def check_fused_dispatch(self, chunk, p_start: int, reseed: bool,
                             positions=None, g_states=None) -> None:
        """``check_pipelined_dispatch`` plus the prompt-chunk bounds the
        fused prefill half enforces — the full pre-broadcast validation
        set for OP_DECODE_PREFILL_FUSED."""
        if not chunk:
            raise ValueError("fused prefill needs a non-empty prompt chunk")
        if len(chunk) > self.max_chunk():
            raise ValueError(
                f"chunk of {len(chunk)} exceeds bucket {self.max_chunk()}"
            )
        if p_start + len(chunk) > self.config.seq_len:
            raise ValueError(
                f"chunk of {len(chunk)} tokens at pos {p_start} exceeds "
                f"seq_len {self.config.seq_len}"
            )
        self.check_pipelined_dispatch(reseed, positions, g_states)

    def decode_prefill_fused(
        self,
        positions: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        p_lane: int = 0,
        chunk: list[int] | None = None,
        p_start: int = 0,
        p_temp: float = 0.0,
        p_topp: float = DEFAULT_TOPP,
        p_seed: int = 0,
        tokens: np.ndarray | None = None,
        g_states: np.ndarray | None = None,
        p_g: int = 0,
    ) -> None:
        """Dispatch ONE fused prefill+decode step into the pipelined ring:
        every generating lane advances one token (the ``decode_pipelined``
        feed rule, carry and all) AND lane ``p_lane`` consumes one bounded
        prompt chunk — the same dispatch, the same compiled program (one
        per prefill bucket). Admissions therefore ride the live chain
        instead of flushing it: the chain's dispatch cadence is untouched
        and ``pipeline_flushes`` stays 0 under steady churn.

        The admitting lane's decode-batch position must park at seq_len
        (callers pass it that way; its junk decode write drops under the
        mode="drop" scatter — the chunk's own KV writes are the real
        ones). The carry slot for ``p_lane`` comes back as the chunk's
        boundary token, so when this is the prompt's final chunk the NEXT
        dispatch can feed the freshly admitted lane straight from device.
        Consume via ``pipeline_consume`` like any other step; the packed
        readback is [2, n+1], the extra column being the boundary
        greedy/sampled pair.

        Junk-KV safety is the ``prefill_chunk`` contract verbatim: padded
        tail writes and any in-flight decode overshoot land in slots that
        are rewritten before any query can read them."""
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        self.check_fused_dispatch(chunk, p_start, tokens is not None,
                                  positions, g_states)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        g_states = self._g_vec(g_states, tokens is not None)
        feed, carry_pos, carry_g = self._pl_feed(tokens, positions)
        bucket = self.bucket_for(len(chunk))
        padded = np.zeros(bucket, np.int32)
        padded[: len(chunk)] = chunk
        nxt, new_pos, new_g, packed, self.cache = self._decode_prefill_fn(
            self.params,
            self.cache,
            feed,
            carry_pos,
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(topps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            jnp.int32(p_lane),
            jnp.asarray(padded),
            jnp.int32(p_start),
            jnp.int32(len(chunk)),
            jnp.float32(p_temp),
            jnp.float32(p_topp),
            jnp.uint32(p_seed & 0xFFFFFFFF),
            self._gtab(),
            carry_g,
            jnp.asarray(g_states, jnp.int32),
            jnp.int32(p_g),
        )
        self._pl_carry = nxt
        self._pl_carry_pos = new_pos
        self._pl_carry_g = new_g
        self._pl_inflight.append(("tok", packed, time.perf_counter()))
        with self.stats.lock:
            self.stats.pipeline_dispatches += 1
            self.stats.fused_steps += 1
            self.stats.sync_bytes_total += self.stats.sync_bytes_per_decode
            self.stats.prefill_tokens += len(chunk)
            self.stats.prefill_bucket_rows += bucket
            self.stats.state_zero_starts += int(self.config.recurrent_state and p_start == 0)
            self.stats.ssm_rows_scanned += len(chunk) * self.config.n_ssm_layers
            self.stats.ssm_rows_computed += bucket * self.config.n_ssm_layers
            self.stats.linear_rows_computed += bucket * self.config.n_linear_layers
            self.stats.delta_rows_computed += bucket * self.config.n_delta_layers
            visited, causal = self._prefill_attn_blocks(p_start, len(chunk), bucket)
            self.stats.prefill_attn_blocks_visited += visited
            self.stats.prefill_attn_blocks_causal += causal
            self.stats.fused_bucket_hist[bucket] = (
                self.stats.fused_bucket_hist.get(bucket, 0) + 1
            )
            d = len(self._pl_inflight)
            self.stats.pipeline_depth_hist[d] = (
                self.stats.pipeline_depth_hist.get(d, 0) + 1
            )

    def pipeline_consume(self):
        """Blocking readback of the OLDEST in-flight pipelined step — the
        lagged half of the pipeline: while this step's tokens cross to the
        host, the younger dispatches keep the device busy.

        Plain/fused steps return (greedy np[n|n+1], sampled np[n|n+1]) —
        the [2, n] token rows, plus the chunk's boundary pair in the extra
        column for a fused step; the token a lane fed into the NEXT
        in-flight step is greedy[i] for temp-0 lanes and sampled[i]
        otherwise (the on-device feed rule). SPEC verify steps
        (``decode_spec_pipelined`` family) return
        (emitted np[n(+1), K+1], n_emit np[n(+1)]) — ``decode_spec``'s
        readback shape, with the boundary pair riding ``emitted[-1, :2]``
        when the step also carried a chunk. Callers know which kind they
        dispatched (the scheduler's meta deque records it)."""
        if not self._pl_inflight:
            raise RuntimeError("pipeline ring empty: nothing to consume")
        faults.fire("engine.consume")  # chaos harness; no-op unarmed
        kind, packed, dispatched_at = self._pl_inflight.popleft()
        t0 = time.perf_counter()
        # dlint: ok[host-sync] the lagged ONE packed int32 readback per pipelined step, counted below
        toks_np = np.asarray(packed)
        t1 = time.perf_counter()
        with self.stats.lock:
            self.stats.host_bytes_in += toks_np.nbytes
            self.stats.decode_s += t1 - t0
            self.stats.decode_steps += 1
            # host time between this step's dispatch and the start of its
            # readback: work the device execution hid (the synchronous path
            # serializes exactly this span)
            self.stats.overlap_s += max(0.0, t0 - dispatched_at)
        if kind == "spec":
            return toks_np[:, :-1], toks_np[:, -1]
        if toks_np.shape[0] > 2:
            # a decode step's counts (a routed FFN's slab reads, an indexer's
            # rows), made on the device, came with the tokens (``_token_rows``)
            def column(col):
                return {name: int(toks_np[2 + i, col]) for i, name in enumerate(self._count_names)}

            got = column(0)
            # a fused step's boundary column: its chunk's TILE_COUNTS, the rest 0
            chunk = column(-1) if toks_np.shape[1] > self.n_lanes else {}
            with self.stats.lock:
                if "slabs" in got:
                    self.stats.moe_slabs_read += got["slabs"]
                    self.stats.moe_assignments += got["assignments"]
                    self.stats.moe_tile_pairs += got["assignments"] + chunk.get("assignments", 0)
                    self.stats.moe_tile_rows += got["tiled_rows"] + chunk.get("tiled_rows", 0)
                    self.stats.moe_slabs_whole += self.moe_slabs_per_step
                    self.stats.moe_rows_unheld += got.get("unheld", 0)
                if "scored" in got:
                    self.stats.indexer_rows_scored += got["scored"]
                    self.stats.sparse_rows_selected += got["selected"]
        return toks_np[0], toks_np[1]

    def pipeline_flush(self, count: bool = True) -> int:
        """Drain every in-flight step (DISCARDING the tokens) and drop the
        device carry; the next dispatch must reseed with host tokens.
        Returns how many steps were discarded. The scheduler drains valid
        chains through ``pipeline_consume`` and only calls this for the
        carry reset, so a non-zero return here means an abort (counted in
        ``stats.pipeline_flushes``). ``count=False`` drains without the
        abort accounting — pod workers' rings lag the root by design, so
        their drain at a clean chain end is expected, not an abort."""
        n = len(self._pl_inflight)
        while self._pl_inflight:
            self.pipeline_consume()
        self._pl_carry = None
        self._pl_carry_pos = None
        self._pl_carry_g = None
        if n and count:
            with self.stats.lock:
                self.stats.pipeline_flushes += 1
        return n

    def pipeline_abort(self) -> int:
        """Containment primitive (the supervised scheduler loop's engine-
        failure path): drop every in-flight step WITHOUT reading anything
        back, and drop the carry. ``pipeline_flush`` drains through
        ``pipeline_consume`` — but after an engine-scoped failure each
        readback of a poisoned step would re-raise the same error, so
        containment must be able to abandon the ring host-side. The
        device buffers are released with the dropped references; the next
        chain reseeds from host tokens like any post-flush dispatch, and
        the affected lanes' KV is treated as garbage (the scheduler
        discards their resident-KV maps). Counts as a pipeline flush —
        an aborted chain is the definition of one."""
        n = len(self._pl_inflight)
        self._pl_inflight.clear()
        self._pl_carry = None
        self._pl_carry_pos = None
        self._pl_carry_g = None
        if n:
            with self.stats.lock:
                self.stats.pipeline_flushes += 1
        return n

    # drafts per speculative step (K = SPEC_DRAFT + 1 verified tokens)
    SPEC_DRAFT = SPEC_DRAFT
    # pod roots forward this via RootControlEngine.__getattr__ and broadcast
    # verify steps as OP_DECODE_SPEC control packets (False on an engine
    # whose model has a recurrent state: __init__)
    supports_speculative = True

    def _check_speculative(self) -> None:
        if not self.supports_speculative:
            if self.config.sparse_attention:
                raise ValueError(
                    "a model with an indexer (index_topk) is served without "
                    "speculation: its selection is computed for one new row "
                    "a lane or for a prompt chunk, not for a verify step's rows"
                )
            raise ValueError(
                "a model with a recurrent per-lane state is served without "
                "speculation: a verify step advances the state by rows it "
                "may reject"
            )

    def decode_spec(
        self,
        tokens: np.ndarray,
        drafts: np.ndarray,
        draft_len: np.ndarray,
        positions: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        g_states: np.ndarray | None = None,
    ):
        """One speculative decode step for all lanes: verifies each lane's
        next token plus up to SPEC_DRAFT drafted continuations in a single
        forward. tokens/positions/draft_len: [n_lanes]; drafts:
        [n_lanes, SPEC_DRAFT]. Greedy lanes emit their plain-decode token
        stream exactly (speculative-verification identity); temp>0 lanes
        must pass draft_len 0 and emit one fused-sampled token.

        Caller contract (per lane): draft_len[i] <= seq_len - positions[i]
        - 1, so every emitted token's logits row has in-bounds KV behind it;
        overshooting draft-slot KV writes are dropped by the cache scatter.
        Returns (step_logits [n, vocab] device array, emitted np[n, K],
        n_emit np[n])."""
        self._check_speculative()
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        if g_states is None:
            g_states = np.zeros(n, np.int32)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        t0 = time.perf_counter()
        logits, packed_out, self.cache = self._decode_spec_fn(
            self.params,
            self.cache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(drafts, jnp.int32),
            jnp.asarray(draft_len, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(topps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            self._gtab(),
            jnp.asarray(g_states, jnp.int32),
        )
        # dlint: ok[host-sync] the ONE [n, K+1] int32 readback per speculative verify step, counted below
        out_np = np.asarray(packed_out)
        emitted, n_emit = out_np[:, :-1], out_np[:, -1]
        with self.stats.lock:
            self.stats.host_bytes_in += out_np.nbytes
            self.stats.decode_s += time.perf_counter() - t0
            self.stats.decode_steps += 1
            self.stats.spec_steps += 1
            self.stats.sync_bytes_total += self.stats.sync_bytes_per_decode
        return logits, emitted, n_emit

    # pod roots broadcast in-chain spec verify steps as
    # OP_DECODE_SPEC_PIPELINED / OP_DECODE_SPEC_PREFILL_FUSED packets
    supports_spec_pipelined = True

    def check_spec_drafts(self, drafts) -> None:
        """THE draft-shape contract, in one place: every spec-pipelined
        entry point (engine dispatch, fused variant, and the pod root's
        pre-broadcast validation) calls this, so a future layout change
        cannot silently diverge one copy from the others."""
        self._check_speculative()
        shape = getattr(drafts, "shape", None)
        want = (self.n_lanes, self.SPEC_DRAFT + 1)
        if shape != want:
            raise ValueError(
                f"spec drafts shape {shape} != {want} (SPEC_DRAFT + 1 "
                "columns: candidate 0 is the host's guess at the carry "
                "token itself)"
            )

    def check_spec_pipelined_dispatch(self, drafts, reseed: bool,
                                      positions=None,
                                      g_states=None) -> None:
        """``check_pipelined_dispatch`` plus the draft-shape contract —
        the full pre-broadcast validation set for OP_DECODE_SPEC_PIPELINED
        (a packet whose root-side compute raises desyncs the pod)."""
        self.check_spec_drafts(drafts)
        self.check_pipelined_dispatch(reseed, positions, g_states)

    def decode_spec_pipelined(
        self,
        positions: np.ndarray,
        drafts: np.ndarray,
        draft_len: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        tokens: np.ndarray | None = None,
        g_states: np.ndarray | None = None,
    ) -> None:
        """Dispatch ONE speculative verify step INTO the pipelined ring —
        the zero-flush composition of ``decode_spec`` and
        ``decode_pipelined``: up to SPEC_DRAFT host-shipped drafts are
        verified against the device's own token carry inside the async
        chain, the per-lane accepted counts advance the POSITION carry
        (``pos + accepted + 1``), and the lagged readback packs
        ``[n, K+1]`` emitted tokens + counts exactly like ``decode_spec``.
        The chain never aborts for a draft hit.

        ``drafts`` is ``[n, SPEC_DRAFT + 1]``: column 0 is the host's
        candidate for the carry token itself (the host's n-gram index is
        one step behind the device — the same lag the consume half already
        models), verified on device before the remaining K count; on a
        reseed the host knows the feed and ships it as candidate 0.
        ``draft_len`` counts the real candidates INCLUDING column 0, so a
        lane needs ``draft_len >= 2`` to possibly accept anything.
        Position semantics are ``decode_pipelined``'s (-1 = device carry).
        Consume via ``pipeline_consume``; junk steps racing a stop follow
        the same discard rule as every pipelined step."""
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        # drafts arrive as a host ndarray from the scheduler's n-gram probe
        # (or the worker's packet slot view); shape-checked, never synced
        self.check_spec_pipelined_dispatch(drafts, tokens is not None,
                                           positions, g_states)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        g_states = self._g_vec(g_states, tokens is not None)
        feed, carry_pos, carry_g = self._pl_feed(tokens, positions)
        nxt, new_pos, new_g, packed, self.cache = self._decode_spec_pl_fn(
            self.params,
            self.cache,
            feed,
            carry_pos,
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(drafts, jnp.int32),
            jnp.asarray(draft_len, jnp.int32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(topps, jnp.float32),
            jnp.asarray(seeds, jnp.uint32),
            self._gtab(),
            carry_g,
            jnp.asarray(g_states, jnp.int32),
        )
        self._pl_carry = nxt
        self._pl_carry_pos = new_pos
        self._pl_carry_g = new_g
        self._pl_inflight.append(("spec", packed, time.perf_counter()))
        with self.stats.lock:
            self.stats.pipeline_dispatches += 1
            self.stats.spec_steps += 1
            self.stats.spec_pipelined_steps += 1
            self.stats.sync_bytes_total += self.stats.sync_bytes_per_decode
            d = len(self._pl_inflight)
            self.stats.pipeline_depth_hist[d] = (
                self.stats.pipeline_depth_hist.get(d, 0) + 1
            )

    def decode_spec_prefill_fused(
        self,
        positions: np.ndarray,
        drafts: np.ndarray,
        draft_len: np.ndarray,
        temps: np.ndarray | None = None,
        topps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,
        p_lane: int = 0,
        chunk: list[int] | None = None,
        p_start: int = 0,
        p_temp: float = 0.0,
        p_topp: float = DEFAULT_TOPP,
        p_seed: int = 0,
        tokens: np.ndarray | None = None,
        g_states: np.ndarray | None = None,
        p_g: int = 0,
    ) -> None:
        """``decode_spec_pipelined`` that ALSO consumes one bounded prompt
        chunk for lane ``p_lane`` — the full zero-flush composition: an
        admitting chunk and a spec verify step share one dispatch, so
        speculation, fused admission, and pipelining multiply instead of
        trading off. Contracts are the union of ``decode_prefill_fused``
        (chunk bounds, boundary-token carry, junk-KV safety) and
        ``decode_spec_pipelined`` (draft alignment, position carry); the
        packed readback is ``[n+1, K+2]`` with the boundary greedy/sampled
        pair in ``emitted[-1, :2]``."""
        n = self.n_lanes
        if temps is None:
            temps = np.zeros(n, np.float32)
        if topps is None:
            topps = np.full(n, DEFAULT_TOPP, np.float32)
        if seeds is None:
            seeds = np.zeros(n, np.uint32)
        # host ndarray from the probe/packet — shape-checked, never synced
        self.check_spec_drafts(drafts)
        self.check_fused_dispatch(chunk, p_start, tokens is not None,
                                  positions, g_states)
        faults.fire("engine.dispatch")  # chaos harness; no-op unarmed
        g_states = self._g_vec(g_states, tokens is not None)
        feed, carry_pos, carry_g = self._pl_feed(tokens, positions)
        bucket = self.bucket_for(len(chunk))
        padded = np.zeros(bucket, np.int32)
        padded[: len(chunk)] = chunk
        nxt, new_pos, new_g, packed, self.cache = (
            self._decode_spec_prefill_fn(
                self.params,
                self.cache,
                feed,
                carry_pos,
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(drafts, jnp.int32),
                jnp.asarray(draft_len, jnp.int32),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(topps, jnp.float32),
                jnp.asarray(seeds, jnp.uint32),
                jnp.int32(p_lane),
                jnp.asarray(padded),
                jnp.int32(p_start),
                jnp.int32(len(chunk)),
                jnp.float32(p_temp),
                jnp.float32(p_topp),
                jnp.uint32(p_seed & 0xFFFFFFFF),
                self._gtab(),
                carry_g,
                jnp.asarray(g_states, jnp.int32),
                jnp.int32(p_g),
            )
        )
        self._pl_carry = nxt
        self._pl_carry_pos = new_pos
        self._pl_carry_g = new_g
        self._pl_inflight.append(("spec", packed, time.perf_counter()))
        with self.stats.lock:
            self.stats.pipeline_dispatches += 1
            self.stats.fused_steps += 1
            self.stats.spec_steps += 1
            self.stats.spec_pipelined_steps += 1
            self.stats.sync_bytes_total += self.stats.sync_bytes_per_decode
            self.stats.prefill_tokens += len(chunk)
            self.stats.prefill_bucket_rows += bucket
            self.stats.fused_bucket_hist[bucket] = (
                self.stats.fused_bucket_hist.get(bucket, 0) + 1
            )
            d = len(self._pl_inflight)
            self.stats.pipeline_depth_hist[d] = (
                self.stats.pipeline_depth_hist.get(d, 0) + 1
            )

    def sample_token(
        self, logits_row, temp: float, topp: float, seed: int, pos: int
    ) -> int:
        """On-device sample from a single [vocab] logits row (the prefill
        boundary token), same kernel as the fused decode sampler."""
        tok = self._sample_one(
            jnp.asarray(logits_row),
            jnp.float32(temp),
            jnp.float32(topp),
            jnp.uint32(seed & 0xFFFFFFFF),
            jnp.int32(pos),
        )
        with self.stats.lock:
            self.stats.host_bytes_in += 4
        return int(tok)  # dlint: ok[host-sync] intentional 4-byte token transfer, counted above

    def collective_stats(self, refresh: bool = False) -> dict:
        """Estimated per-decode-step collective traffic from the compiled
        program's post-SPMD HLO — the analogue of the reference's per-socket
        byte counters (src/nn/nn-network.cpp:493-508). Returns {} off-mesh."""
        if self.mesh is None:
            return {}
        if getattr(self, "_coll_stats", None) is not None and not refresh:
            return self._coll_stats
        from ..parallel.comm_stats import collective_stats_of_compiled

        n = self.n_lanes
        z = np.zeros(n, np.int32)
        zf = np.zeros(n, np.float32)
        compiled = self._decode_fn.lower(
            self.params,
            self.cache,
            jnp.asarray(z),
            jnp.asarray(z),
            jnp.asarray(zf),
            jnp.asarray(zf),
            jnp.asarray(z.astype(np.uint32)),
            self._gtab(),
            jnp.asarray(z),
        ).compile()
        stats = collective_stats_of_compiled(compiled)
        # stamp the dequant path the compiled step bakes in (static
        # argname): per-step traffic numbers are only comparable across
        # runs when the kernel mode they were measured under is recorded
        from ..ops import pallas_q40

        stats["dequant_mode"] = pallas_q40.DEQUANT_MODE
        # keep the executable for dispatch: decode shapes never change, so
        # this one AOT compile replaces the jit path's own compile
        self._decode_exec = compiled
        with self.stats.lock:
            self.stats.sync_bytes_per_decode = stats.get("total_bytes", 0)
            self.stats.sync_collectives_per_decode = stats.get("n_collectives", 0)
        self._coll_stats = stats
        return stats

    def measured_sync_stats(self, steps: int = 4) -> dict:
        """MEASURED per-decode-step time split from a profiler trace
        (parallel/comm_stats.measured_step_breakdown): device busy ms and
        collective (sync) ms per step — the measured analogue of the
        reference's per-token Sync readout (src/dllama.cpp:54-64), vs the
        static byte estimate of ``collective_stats``.

        Benchmark probe: it runs the decode step with zero tokens at
        position 0 on every lane, which REWRITES cache slot 0 — call it
        before serving or after generation, not mid-request."""
        from ..parallel.comm_stats import measured_step_breakdown

        z = np.zeros(self.n_lanes, np.int32)
        zf = np.zeros(self.n_lanes, np.float32)
        zu = np.zeros(self.n_lanes, np.uint32)

        def step():
            # decode returns host numpy for greedy, so it has already blocked
            self.decode(z, z, zf, zf, zu)

        with self.stats.preserved():
            return measured_step_breakdown(step, steps=steps)

    def lane_logits(self, logits, lane: int) -> np.ndarray:
        """Transfer one lane's logits to host (counted, for sampling)."""
        faults.fire("engine.transfer")  # chaos harness; no-op unarmed
        # dlint: ok[host-sync] sanctioned [vocab] f32 transfer API: the choke point that counts the bytes
        out = np.asarray(logits[lane])
        with self.stats.lock:
            self.stats.host_bytes_in += out.nbytes
        return out

    def all_logits(self, logits) -> np.ndarray:
        """Single batched device->host transfer of all lanes' logits."""
        faults.fire("engine.transfer")  # chaos harness; no-op unarmed
        # dlint: ok[host-sync] sanctioned batched [n, vocab] f32 transfer API: the choke point that counts the bytes
        out = np.asarray(logits)
        with self.stats.lock:
            self.stats.host_bytes_in += out.nbytes
        return out

    def copy_lane(self, src: int, dst: int,
                  prefix_len: int | None = None) -> None:
        """Copy lane ``src``'s whole KV cache into lane ``dst`` (prefix
        caching on the CONTIGUOUS layout: a new request sharing a prompt
        prefix with tokens already resident in ``src`` skips prefilling
        that prefix — the scheduler tracks which tokens each lane's cache
        holds and calls this before prefilling only the tail). No
        reference analogue: its lanes share one cache (defect (c)), so
        prefix reuse is impossible there.

        ``prefix_len`` (when the caller knows it) lets a zero-length
        share short-circuit like ``src == dst`` does: both used to
        rebuild the whole cache pytree for a copy that moves nothing.
        Paged engines refuse outright — sharing there is a refcount bump
        on the SAME physical pages (``paged_admit``), and a whole-lane
        HBM copy is exactly the cost the paged layout exists to avoid."""
        if self.kvpool is not None:
            raise RuntimeError(
                "copy_lane is the contiguous layout's primitive; a paged "
                "engine shares prefix pages by refcount via paged_admit"
            )
        if self.config.recurrent_state and src != dst:
            raise RuntimeError(
                "copy_lane would give the lane the source's recurrent state "
                "at ITS last position, not at the shared prefix: a model "
                "with such a state prefills every prompt whole"
            )
        if src == dst or prefix_len == 0:
            return  # nothing would move: skip the whole-cache rebuild
        self.cache = self._copy_lane_fn(
            self.cache, jnp.int32(src), jnp.int32(dst)
        )

    # -- paged KV pool (runtime/kvpool.py): the host/device seam ------------

    def _paged_table_row(self, blocks) -> np.ndarray:
        """A lane's page-table row (the pool's shared encoding recipe,
        ``kvpool.table_row``) as the int32 device-leaf dtype."""
        # dlint: ok[host-sync] host int list -> numpy row; no device value involved
        return np.asarray(self.kvpool.table_row(list(blocks)), np.int32)

    def _replace_leaf(self, host_array, sharding):
        """THE sanctioned device-leaf constructor — the ``engine.py``
        aval-stability rule promoted from a comment into code (PR 11's
        review found the failure by hand; dlint's ``jit-stability``
        check now whitelists exactly this function). Every device-pytree
        leaf rebuilt between dispatches (the page-table row, the grammar
        slab tables) MUST come through here:

        - off-mesh (``sharding is None``): a plain ``jnp.asarray`` of
          the host mirror — same shape/dtype, so the leaf's aval is
          unchanged by construction;
        - on a mesh: ``make_array_from_callback`` with the NamedSharding
          captured at init, built from each process's (identical) host
          mirror — the ONLY form that both preserves the compiled
          programs' input aval (a bare ``jnp.asarray`` would drop the
          sharding and force a recompile per replacement on a
          single-host mesh) and works on multi-process pods where the
          mesh is not fully addressable."""
        if sharding is None:
            return jnp.asarray(host_array)
        return jax.make_array_from_callback(
            host_array.shape, sharding, lambda idx: host_array[idx]
        )

    def _table_leaf(self):
        """The host table mirror as the cache pytree's table leaf, via
        the sanctioned sharding-preserving constructor."""
        return self._replace_leaf(self._host_tables, self._table_sharding)

    def apply_paged_admit(self, lane: int, row, copies) -> None:
        """Device half of a paged admission (or release): apply the COW
        page ``copies`` then ship lane ``lane``'s new table ``row`` — both
        thread the donated cache pytree, so they are ordered BEFORE any
        later-dispatched tail prefill/decode by construction. Split from
        ``paged_admit`` so pod workers can replay it from OP_KV_TABLE
        packets while the pool bookkeeping stays root-only."""
        for src, dst in copies:
            self.cache = self._copy_page_fn(
                self.cache, jnp.int32(src), jnp.int32(dst)
            )
        self._host_tables[lane] = row
        # a table update between dispatches is just a new pytree leaf
        # (host->device, a few KB of int32 — never a device sync)
        self.cache = self.cache._replace(table=self._table_leaf())

    def paged_admit(self, lane: int, tokens, reserve_tokens: int,
                    min_share_tokens: int = 1) -> int:
        """Reserve lane ``lane``'s pages for a request (prompt ``tokens``,
        whole potential range ``reserve_tokens``) and apply the device
        half. Returns ``start`` — prompt tokens already resident via the
        prefix tree (refcount bumps on SHARED pages, zero HBM copies,
        plus at most one single-page COW at the divergent block); the
        caller prefills only ``tokens[start:]``. Raises
        :class:`~.kvpool.PoolExhausted` when the pool cannot serve the
        reservation even after evicting parked sessions.

        Tiered residency ordering: (1) the pool admission may evict
        parked pages and stage them for swap-out; (2) those stage
        entries DRAIN (device gather -> host tier) before anything
        writes — the gather dispatches first, so it reads pre-eviction
        bytes even when an evicted page was immediately re-popped as
        this admission's fresh page; (3) host-tier hits scatter back in
        (``swapins``); (4) COW copies + the table row apply. All four
        thread the donated cache pytree, so the tail prefill can never
        observe a half-applied admission."""
        start, blocks, copies, swapins = self.kvpool.admit(
            lane, tokens, reserve_tokens, min_share_tokens
        )
        self.drain_kv_swapouts()
        if swapins:
            self.swap_in_pages([p for p, _ in swapins],
                               [b for _, b in swapins])
        self.apply_paged_admit(lane, self._paged_table_row(blocks), copies)
        return start

    def paged_commit(self, lane: int, tokens) -> None:
        """Register lane ``lane``'s committed history into the prefix tree
        (host bookkeeping only — the KV bytes are already on device)."""
        self.kvpool.commit(lane, tokens)

    def paged_finish(self, lane: int, park: bool = True) -> None:
        """Release lane ``lane``'s pages at request end. ``park=True``
        keeps its tree-registered blocks resident (refcounted, LRU-
        bounded) so follow-ups and same-prompt admissions share copy-free;
        ``park=False`` frees everything (failure path). The lane's table
        row resets to all-unmapped — skipped entirely when the lane never
        mapped anything (the exhaustion-shed reject path), so overload
        rejects stay host-only cheap. Parking may overflow the LRU bound
        and stage swap-outs — drained here, before the unmap's table
        write could be followed by page-reusing dispatches."""
        held = self.kvpool.finish(lane, park=park)
        self.drain_kv_swapouts()
        if held:
            self.apply_paged_admit(lane, self._paged_table_row([]), [])

    def paged_unmap_all(self) -> None:
        """Device half of the paged reset: every lane's table row goes
        all-unmapped. Split from :meth:`paged_reset` so pod workers can
        replay it from an OP_KV_TABLE reset packet (lane == -1) while the
        pool bookkeeping stays root-only."""
        self._host_tables[:] = self.kvpool.table_row([])
        self.cache = self.cache._replace(table=self._table_leaf())

    def paged_reset(self) -> None:
        """Containment: after an engine-scoped failure the device pool
        contents are not trusted — drop every mapping, parked session and
        tree node, and unmap every lane's table row."""
        self.kvpool.reset()
        self.paged_unmap_all()

    def pool_stats(self) -> dict:
        """Page-pool pressure snapshot for /stats (bridged to /metrics);
        ``{}`` on contiguous engines. Merges the engine's swap-traffic
        counters next to the pool's host-tier gauges so the whole tier
        story reads off one surface."""
        if self.kvpool is None:
            return {}
        out = self.kvpool.stats()
        out.update({
            "swap_ins": self.swap_ins,
            "swap_outs": self.swap_outs,
            "swap_in_bytes": self.swap_in_bytes,
            "swap_out_bytes": self.swap_out_bytes,
            "swap_in_ms": round(self.swap_in_ms, 3),
        })
        return out

    def _page_leaf_geometry(self) -> tuple[tuple, "np.dtype"]:
        """One page's K (or V) leaf shape/dtype: ``[L, page_size,
        n_kv_heads, head_size]`` sliced out of the pool axis."""
        k = self.cache.k
        return (k.shape[0],) + tuple(k.shape[2:]), np.dtype(k.dtype)

    def export_kv_page(self, page: int) -> bytes:
        """Serialize physical page ``page``'s K/V bytes (K then V, raw
        row-major) for cross-replica transfer (disagg/kvtransfer.py).
        A host sync by design — the disagg hand-off IS a host round
        trip, and it only runs on committed (immutable) pages, so the
        bytes are stable while the source lane keeps decoding."""
        if self.kvpool is None:
            raise RuntimeError("export_kv_page needs a paged engine")
        # dlint: ok[host-sync] sanctioned disagg export choke point: one committed page's K/V leaves the device here
        k = np.asarray(self.cache.k[:, page])
        # dlint: ok[host-sync] second half of the same sanctioned page export
        v = np.asarray(self.cache.v[:, page])
        return k.tobytes() + v.tobytes()

    def import_kv_page(self, page: int, payload: bytes) -> None:
        """Write a transferred page's K/V bytes into physical page
        ``page`` (the inverse of :meth:`export_kv_page`), through the
        warmed single-page write program — the donated cache pytree
        orders it before any later-dispatched prefill/decode, exactly
        like a COW copy. Raises ``ValueError`` on a size mismatch
        (geometry-skewed peer) before touching the device."""
        if self.kvpool is None:
            raise RuntimeError("import_kv_page needs a paged engine")
        shape, dtype = self._page_leaf_geometry()
        half = int(np.prod(shape)) * dtype.itemsize
        if len(payload) != 2 * half:
            raise ValueError(
                f"kv page payload is {len(payload)} bytes, expected "
                f"{2 * half} for page geometry {tuple(shape)} {dtype}"
            )
        k_page = np.frombuffer(payload[:half], dtype=dtype).reshape(shape)
        v_page = np.frombuffer(payload[half:], dtype=dtype).reshape(shape)
        self.cache = self._write_page_fn(
            self.cache, jnp.int32(page), k_page, v_page
        )

    # -- tiered KV residency: the device halves of the host swap tier -------

    def swap_out_pages(self, pages) -> list:
        """Batched device->host read of physical pages' K/V bytes for the
        swap tier — ``export_kv_page``'s encoding (K then V, raw
        row-major per page) at ``_SWAP_BATCH`` pages per dispatch, so
        swapping a whole evicted chain costs ceil(n/_SWAP_BATCH) device
        programs instead of n. A host sync by design, like the disagg
        export: the pages just LEFT the pool (or are committed and
        immutable), so the bytes are stable."""
        if self.kvpool is None:
            raise RuntimeError("swap_out_pages needs a paged engine")
        out: list = []
        for off in range(0, len(pages), _SWAP_BATCH):
            chunk = [int(p) for p in pages[off: off + _SWAP_BATCH]]  # dlint: ok[host-sync] page ids are host ints from the pool, never device values
            n = len(chunk)
            # dlint: ok[host-sync] host int list -> fixed-shape index operand; no device value involved
            idx = np.asarray(
                (chunk + [chunk[0]] * _SWAP_BATCH)[:_SWAP_BATCH], np.int32
            )
            k_g, v_g = self._gather_pages_fn(self.cache, idx)
            # dlint: ok[host-sync] sanctioned swap-out choke point: evicted committed pages' K/V leave the device here
            k_h = np.asarray(k_g)
            # dlint: ok[host-sync] second half of the same sanctioned swap-out gather
            v_h = np.asarray(v_g)
            for i in range(n):
                out.append(k_h[:, i].tobytes() + v_h[:, i].tobytes())
        return out

    def swap_in_pages(self, pages, payloads) -> None:
        """Batched host->device write reactivating swapped pages (the
        inverse of :meth:`swap_out_pages`): every payload is
        size-validated against the page-leaf geometry BEFORE anything
        dispatches (a geometry-skewed payload must not half-apply), then
        the chunked scatter threads the donated cache pytree — ordered
        before any later-dispatched tail prefill, exactly like a COW
        copy. Raises ``ValueError`` on a size or count mismatch."""
        if self.kvpool is None:
            raise RuntimeError("swap_in_pages needs a paged engine")
        if len(pages) != len(payloads):
            raise ValueError(
                f"swap_in_pages: {len(pages)} pages vs "
                f"{len(payloads)} payloads"
            )
        if not pages:
            return
        shape, dtype = self._page_leaf_geometry()
        half = int(np.prod(shape)) * dtype.itemsize
        for i, payload in enumerate(payloads):
            if len(payload) != 2 * half:
                raise ValueError(
                    f"swap_in_pages: payload {i} is {len(payload)} bytes, "
                    f"expected {2 * half} for page geometry "
                    f"{tuple(shape)} {dtype}"
                )
        t0 = time.perf_counter()
        for off in range(0, len(pages), _SWAP_BATCH):
            chunk_p = [int(p) for p in pages[off: off + _SWAP_BATCH]]  # dlint: ok[host-sync] page ids are host ints from the pool, never device values
            chunk_b = list(payloads[off: off + _SWAP_BATCH])
            while len(chunk_p) < _SWAP_BATCH:  # idempotent duplicate pad
                chunk_p.append(chunk_p[0])
                chunk_b.append(chunk_b[0])
            idx = np.asarray(chunk_p, np.int32)  # dlint: ok[host-sync] host int list -> index operand; no device value involved
            k_stack = np.stack(
                [np.frombuffer(b[:half], dtype=dtype).reshape(shape)
                 for b in chunk_b], axis=1,
            )
            v_stack = np.stack(
                [np.frombuffer(b[half:], dtype=dtype).reshape(shape)
                 for b in chunk_b], axis=1,
            )
            self.cache = self._scatter_pages_fn(
                self.cache, idx, k_stack, v_stack
            )
        self.swap_ins += len(pages)
        self.swap_in_bytes += sum(len(b) for b in payloads)
        self.swap_in_ms += (time.perf_counter() - t0) * 1000.0

    def drain_kv_swapouts(self) -> int:
        """Move the pool's staged swap-outs into the host tier: take the
        pending ``(node_key, block, page)`` triples, read the pages in
        batched device gathers, and store each payload under its chain
        key. Runs inside every paged mutation point (admit/finish/
        swap_out_parked) BEFORE any device write that could reuse the
        freed pages. Best-effort cache with strict accounting: a failed
        device read discards the batch (the tier just misses — the
        sessions rebuild from the journal as before) and re-raises for
        engine-scoped containment; an over-budget ``put`` simply drops.
        Returns how many pages the tier actually stored."""
        if self.kvpool is None:
            return 0
        tier = self.kvpool.host_tier
        if not tier.enabled:
            return 0
        pending = self.kvpool.take_pending_swapouts()
        if not pending:
            return 0
        try:
            payloads = self.swap_out_pages([p for _, _, p in pending])
        except BaseException:
            for node_key, _blk, _page in pending:
                tier.discard(node_key)
            raise
        stored = 0
        for (node_key, blk, _page), payload in zip(pending, payloads):
            if tier.put(node_key, blk, payload):
                stored += 1
        self.swap_outs += len(pending)
        self.swap_out_bytes += sum(len(b) for b in payloads)
        return stored

    def swap_out_parked(self) -> int:
        """Evict every parked session straight into the host tier (the
        tests' lever for the middle residency tier; pressure
        eviction takes the same path organically). Returns how many
        sessions were evicted."""
        if self.kvpool is None:
            return 0
        n = self.kvpool.swap_out_parked()
        self.drain_kv_swapouts()
        return n

    def reset_swap_stats(self) -> None:
        """Zero the swap-traffic counters (warmup drops its own warm
        dispatch from them, like reset_worker_stats for pod counters —
        a METHOD so pod proxies reach the owning engine's attributes)."""
        self.swap_ins = 0
        self.swap_outs = 0
        self.swap_in_bytes = 0
        self.swap_out_bytes = 0
        self.swap_in_ms = 0.0

    def reset_lane(self, lane: int) -> None:
        """Nothing to clear on device: a fresh request's prefill rewrites the
        lane's cache from position 0, and reads are masked to s <= pos. A
        state that is overwritten in place (models/hybrid.py) is not cleared
        either: a step whose first position is 0 reads zeros in its place."""


@contextlib.contextmanager
def _warming_program(name: str):
    """One ``warmup_program`` start-up line per program ``warmup_engine``
    warms: its name, the host seconds its calls took (trace, compile or
    cache load, dispatch; an asynchronous program may still be executing
    when the line is written) and whether XLA compiled it or the
    persistent cache served it — the split of ``setup_s`` by program."""
    c0, h0 = jitcheck.total_compiles(), jitcheck.cache_counts()
    t0 = time.perf_counter()
    yield
    seconds = time.perf_counter() - t0
    hits = jitcheck.cache_counts()["compile_cache_hits"] - h0["compile_cache_hits"]
    # jax's compile event covers compile-OR-load-from-the-persistent-cache
    compiled = max(0, jitcheck.total_compiles() - c0 - hits)
    log_event(
        "warmup_program", program=name, seconds=round(seconds, 3),
        compiled=compiled, cache_hits=hits,
        source="compiled" if compiled else ("cache" if hits else "memory"),
    )


def warmup_engine(
    engine, spec: bool = True, multi_step: int = 0, pipeline: bool = True
) -> None:
    """Compile every serving program up front (each prefill bucket, decode
    with AND without the logits output, the speculative verify step, every
    multi-step horizon bucket the scheduler can pick, the pipelined step,
    the fused prefill+decode step per bucket, and — paged engines — the
    single-page COW copy) so the first real request doesn't pay XLA
    compiles mid-service — the analogue of the reference finishing its
    executor build before accepting connections (src/app.cpp:233-312).

    Deliberately a FREE function driving the PUBLIC engine API: on a
    multi-host pod root the proxy's decode/prefill_chunk broadcast control
    packets so workers replay the same compiles; an InferenceEngine method
    reached through the proxy's __getattr__ would bypass the broadcast and
    deadlock the mesh. The junk KV writes land in uncommitted slots
    (admission rewrites from position 0) and the stats counters are
    restored afterwards."""
    n = engine.n_lanes
    z = np.zeros(n, np.int32)
    from ..ops import pallas_q40

    jitcheck.install()  # count this warm-up's own compiles and cache loads
    # warmup's own compiles are the sanctioned ones: pause the recompile
    # witness for the duration (tests warm several engines per process —
    # one engine's warmup must not fire another's armed witness); arming
    # for THIS engine happens at the end, once every program is compiled
    with jitcheck.warming(), engine.stats.preserved():
        for bucket in engine.prefill_buckets:
            with _warming_program(f"prefill[{bucket}]"):
                engine.prefill_chunk(0, [0] * bucket, 0)
        with _warming_program("decode"):
            engine.decode(z, z)
        # the serving loop's common step materializes no logits — a
        # distinct program that would otherwise compile mid-request
        with _warming_program("decode_nologits"):
            engine.decode(z, z, want_logits=False)
        if spec and getattr(engine, "supports_speculative", False):
            with _warming_program("decode_spec"):
                engine.decode_spec(
                    z, np.zeros((n, engine.SPEC_DRAFT), np.int32), z, z
                )
        if multi_step > 1 and getattr(engine, "supports_multi_step", False):
            from .spec import pow2_floor

            # the WHOLE horizon set the scheduler dispatches: every
            # power-of-two bucket down to 2 (batch endgames shrink the
            # horizon, and a lazily compiled h charges first-request
            # latency mid-service)
            h = pow2_floor(multi_step)
            while h > 1:
                with _warming_program(f"decode_multi[{h}]"):
                    engine.decode_multi(z, z, h=h)
                h //= 2
        if (
            pipeline
            and getattr(engine, "supports_pipelined", False)
            and getattr(engine, "pipeline_depth", 0) > 1
        ):
            # each pipelined family is warmed TWICE: the reseed form
            # (host-array feed/positions) and the CHAINED form
            # (positions -1 = read the device carry). On a mesh these
            # are DIFFERENT compiled programs — the chained dispatch's
            # feed/carry operands arrive with the replicated
            # NamedSharding the previous step produced, not host
            # arrays — so warming only the reseed left the first live
            # chained step of every pod serving loop paying an XLA
            # compile mid-service (found by the DLLAMA_JITCHECK witness
            # on the virtual pod; single-chip engines hit one program
            # for both forms). The ring is depth >= 2 here, so the
            # chained dispatch fits before the flush.
            neg = np.full(n, -1, np.int32)
            with _warming_program("decode_pl"):
                engine.decode_pipelined(z, tokens=z)
                engine.decode_pipelined(neg)
                engine.pipeline_flush()
            spec_pl = bool(
                spec and getattr(engine, "supports_spec_pipelined", False)
            )
            if spec_pl:
                # the in-chain spec verify step: the first draft hit in a
                # live chain must not eat an XLA compile — reseed AND
                # chained forms, like the plain pipelined step
                k1 = engine.SPEC_DRAFT + 1
                with _warming_program("decode_spec_pl"):
                    engine.decode_spec_pipelined(
                        z, np.zeros((n, k1), np.int32), z, tokens=z
                    )
                    engine.decode_spec_pipelined(
                        neg, np.zeros((n, k1), np.int32), z
                    )
                    engine.pipeline_flush()
            if getattr(engine, "supports_fused_prefill", False):
                # the fused prefill+decode family compiles per bucket —
                # without this, the FIRST admission into a live chain
                # pays a fresh XLA compile exactly when lanes are hot.
                # Admissions ride the LIVE chain by design, so the
                # chained form is the one serving actually dispatches —
                # warm it behind each bucket's reseed form.
                park = np.full(n, engine.config.seq_len, np.int32)
                for bucket in engine.prefill_buckets:
                    with _warming_program(f"decode_prefill[{bucket}]"):
                        engine.decode_prefill_fused(
                            park, p_lane=0, chunk=[0] * bucket, tokens=z,
                        )
                        engine.decode_prefill_fused(
                            neg, p_lane=0, chunk=[0] * bucket,
                        )
                        engine.pipeline_flush()
                    if spec_pl:
                        # admitting chunk + spec verify sharing a dispatch
                        # compiles per bucket too — both forms again
                        with _warming_program(f"decode_spec_prefill[{bucket}]"):
                            engine.decode_spec_prefill_fused(
                                park, np.zeros((n, k1), np.int32), z,
                                p_lane=0, chunk=[0] * bucket, tokens=z,
                            )
                            engine.decode_spec_prefill_fused(
                                neg, np.zeros((n, k1), np.int32), z,
                                p_lane=0, chunk=[0] * bucket,
                            )
                            engine.pipeline_flush()
        pool = getattr(engine, "kvpool", None)
        apply_paged = getattr(engine, "apply_paged_admit", None)
        if pool is not None and apply_paged is not None:
            # the single-page COW program: the first divergent-block
            # admission must not eat an XLA compile mid-service. Page 0
            # onto itself copies zeros over zeros through the real
            # program, and the all-sentinel row leaves lane 0's table in
            # its initial unmapped state (pod roots broadcast via the
            # RootControlEngine override so workers compile too)
            with _warming_program("copy_page"):
                apply_paged(
                    0,
                    np.full(pool.blocks_per_lane, pool.n_pages, np.int32),
                    [(0, 0)],
                )
            exp = getattr(engine, "export_kv_page", None)
            imp = getattr(engine, "import_kv_page", None)
            if callable(exp) and callable(imp):
                # the disagg page-write program: the first adopted page
                # must not eat an XLA compile mid-service. Page 0's own
                # zeros ride back over themselves through the real
                # program (pod roots broadcast via the RootControlEngine
                # override so workers compile too).
                with _warming_program("write_page"):
                    imp(0, exp(0))
            swap_out = getattr(engine, "swap_out_pages", None)
            swap_in = getattr(engine, "swap_in_pages", None)
            if callable(swap_out) and callable(swap_in):
                # the batched swap gather/scatter programs (host tier):
                # the first pressure eviction / host-tier reactivation
                # must not eat an XLA compile mid-service. Page 0's own
                # zeros ride out and back through the real programs —
                # batch padding makes this the same compiled shape as
                # any real batch (pod roots broadcast the swap-in via
                # the RootControlEngine override so workers compile too).
                with _warming_program("swap_pages"):
                    swap_in([0], swap_out([0]))
                reset_swap = getattr(engine, "reset_swap_stats", None)
                if callable(reset_swap):
                    reset_swap()
        recurrent = getattr(getattr(engine, "config", None), "recurrent_state", False)
        if pool is None and n > 1 and not recurrent:
            # the contiguous prefix-reuse primitive (never taken, and refused,
            # for a model whose lanes carry a state overwritten in place) (found by dlint's
            # warmup-coverage at adoption): the first shared-prefix
            # admission used to pay the whole-lane-copy compile
            # mid-serving. Traced src/dst scalars: ONE program for any
            # pair; lane 1's junk is rewritten by its next admission.
            with _warming_program("copy_lane"):
                engine.copy_lane(0, 1)
        # the host-exact escape hatch's standalone sampler (same
        # adoption finding): one [vocab] program, pennies to warm
        with _warming_program("sample_one"):
            engine.sample_token(
                np.zeros(engine.config.vocab_size, np.float32),
                0.7, 0.9, 1, 0,
            )
    # pod roots: drop the replayed warmup traffic from worker counters too
    reset_workers = getattr(engine, "reset_worker_stats", None)
    if reset_workers is not None:
        reset_workers()
    # one structured line deployments verify engine config from logs alone
    # (telemetry/logs.py; the scheduler-side twin is scheduler_start)
    mesh = getattr(engine, "mesh", None)
    # mesh engines: AOT-compile the decode step NOW (outside preserved(), so
    # the sync_bytes_per_decode estimate survives into serving) — the first
    # pod dispatch must not pay the compile, and /stats should report the
    # per-step collective payload from the start
    if mesh is not None:
        coll = getattr(engine, "collective_stats", None)
        if callable(coll):
            with jitcheck.warming():
                try:
                    coll()
                except Exception as e:  # noqa: BLE001 — the probe is evidence, never a startup blocker
                    log_event(
                        "collective_probe_failed",
                        error=f"{type(e).__name__}: {e}",
                    )
    # from here on a new XLA backend compile is a broken invariant: every
    # one bumps stats.jit_compiles_after_warmup (surfaced on /stats,
    # bridged to /metrics, reported by the benchmark), and under
    # DLLAMA_JITCHECK=1 raises RecompileAfterWarmup at the guilty
    # dispatch — the runtime twin of the warmup-coverage/jit-stability
    # static checks (analysis/jitcheck.py, docs/LINT.md)
    jitcheck.arm(engine.stats)
    pipelined = bool(
        pipeline
        and getattr(engine, "supports_pipelined", False)
        and getattr(engine, "pipeline_depth", 0) > 1
    )
    from ..ops.ring_collective import ring_sync_enabled

    log_event(
        "warmup_engine",
        n_lanes=n,
        buckets_warmed=list(engine.prefill_buckets),
        mesh_shape=dict(mesh.shape) if mesh is not None else None,
        ring_sync=bool(mesh is not None and ring_sync_enabled()),
        pipeline_depth=getattr(engine, "pipeline_depth", 0),
        pipelined=pipelined,
        # fused admissions need the live pipeline (and were only warmed
        # under it) — same gate the scheduler's _fused_ok applies, so
        # this line and scheduler_start cannot contradict each other
        fused_prefill=bool(
            pipelined and getattr(engine, "supports_fused_prefill", False)
        ),
        multi_step=multi_step,
        speculative=bool(
            spec and getattr(engine, "supports_speculative", False)
        ),
        # drafts verified INSIDE the pipelined chain (zero-flush serving)
        spec_pipelined=bool(
            pipelined
            and spec
            and getattr(engine, "supports_spec_pipelined", False)
        ),
        # the recompile witness is armed (counting) from here on; strict
        # means DLLAMA_JITCHECK=1 will raise on any post-warmup compile
        jitcheck_strict=jitcheck.enabled(),
        seq_len=engine.config.seq_len,
        # which attention and which expert path the warmed decode steps run
        # (and what is declined for a recurrent state): said here too, so
        # that a start without load_stack's runtime_device line says it
        **(engine.path_facts() if callable(getattr(engine, "path_facts", None)) else {}),
        # the dequant arithmetic every warmed program baked in (a static
        # argument of the Q40 matmul's jit)
        dequant_mode=pallas_q40.DEQUANT_MODE,
    )
