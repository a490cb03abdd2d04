"""The comparison that decides ``correct``: engine against plain reference.

Outside the timed window, at the configuration's own widths, in two parts.

**Logits.** A seeded sample of sequences (lengths in the configuration file,
chosen to pass through every prefill bucket the traffic uses and through
chunked prefill) is prefilled into lanes of the engine, a few prefixes of each
into a spare lane as prompts of their own, then decoded through the cache for
some steps with all sample lanes in one batch, teacher-forced with seeded
tokens. The reference makes one full forward pass over prompt + forced tokens
(a prefix's last row is a row of the same pass: attention is causal). Logits
are compared, not tokens: on random weights the largest logit changes on
rounding. Per row of logits the number is the relative error of the centred
rows, ``|| (e - mean e) - (r - mean r) || / || r - mean r ||`` (a shift of a
whole row changes no probability). Compared with the limits are the root mean
square of that number over the prefill rows and over the decode rows: a mean
over some tens of rows moves a few percent from seed to seed where the largest
row moves fifteen, and the step below bfloat16 that has to fail, Q80-emulated
activations, reads only 1.3 times the engine.

**Routes.** The logits come from the synchronous programs (`engine.prefill`,
`engine.decode`); the window runs their siblings, the pipelined decode step
and the fused prefill + decode step, which share the core and differ in the
feed (the device's carry of token and position), the splice of the admitted
lane and the sampler. So a short chain is dispatched as the scheduler
dispatches it, two deep: a reseeded pipelined step; a fused step that admits
one sample prompt whole into a spare lane; two fused steps that admit another
in two chunks, between which the lane parks as an admitting lane does; a
chained step that decodes both from the carry. Half the lanes are greedy,
half sampled. No lane is ever rewound: the chain runs on one set of lanes and
the synchronous programs it is held against on their *twins*, lanes that
`engine.prefill` filled with the same prompts before the chain, that stood
parked while it ran, and that `engine.decode` then steps with the tokens the
chain chose, the chain's lanes parked in their turn. Compared: each greedy
token of the chain against the twin's logits (how far under the row's largest
logit it lies, in standard deviations of the row: 0 unless two logits tie);
each sampled token against the nucleus computed here on the host from those
logits (how far past ``top_p`` the probability before it reaches: 0 inside
the nucleus); the boundary token of each admission likewise against the
logits `engine.prefill` gave its twin; and, pair by pair, the whole per-lane
state of a chain lane against its twin's, which has absorbed the same tokens:
whatever the state is made of, rows that a step could write again or a
running sum that it could not.

The model is reached through the configuration's family module alone
(`cells.load_family`): its plain reference and its comparison of two lanes'
state. Nothing here knows an architecture.
"""

from __future__ import annotations

import time

import numpy as np


def sample_sequences(cfg: dict, seed: int):
    """(prompts, forced): token ids from the seed at the lengths the
    configuration file states."""
    spec = cfg["correctness"]
    steps = int(spec["decode_steps"])
    rng = np.random.default_rng([int(seed), 4])
    vocab = cfg["vocab_size"]
    prompts = [
        [int(x) for x in rng.integers(2, vocab, size=int(n))]
        for n in spec["prompt_tokens"]
    ]
    forced = [
        [int(x) for x in rng.integers(2, vocab, size=steps)] for _ in prompts
    ]
    return prompts, forced


def prefix_lengths(cfg: dict, n: int) -> list[int]:
    """The lengths at which a prompt of n tokens is also prefilled alone."""
    return [max(1, int(n * s)) for s in cfg["correctness"].get("prefix_shares", [])]


def engine_logits(engine, prompts, forced, prefixes):
    """Prefill each prompt into its own lane (and its prefixes into the last
    lane), then decode the forced tokens through the cache, all sample lanes
    in one batch. Returns float32 ``[B, P + 1 + steps, vocab]``: the last
    position of each prefix and of the prompt, then each step."""
    n = engine.n_lanes
    if len(prompts) > n - 2:
        raise ValueError(f"{len(prompts)} sample sequences and two spare lanes "
                         f"need {len(prompts) + 2} lanes, have {n}")
    steps = len(forced[0])
    seq_len = engine.config.seq_len
    rows = [[] for _ in prompts]
    for lane, prompt in enumerate(prompts):
        for n_p in prefixes[lane]:
            last, _greedy, _pos = engine.prefill(n - 1, prompt[:n_p])
            rows[lane].append(np.asarray(last, np.float32))
        last, _greedy, _pos = engine.prefill(lane, prompt)
        rows[lane].append(np.asarray(last, np.float32))
    for j in range(steps):
        tokens = np.zeros(n, np.int32)
        # lanes outside the sample point past the context: their cache
        # writes are dropped, as the scheduler parks idle lanes
        positions = np.full(n, seq_len, np.int32)
        for lane, prompt in enumerate(prompts):
            tokens[lane] = forced[lane][j]
            positions[lane] = len(prompt) + j
        logits, _g, _s = engine.decode(tokens, positions, want_logits=True)
        logits = np.asarray(logits, np.float32)
        for lane in range(len(prompts)):
            rows[lane].append(logits[lane])
    return np.stack([np.stack(r) for r in rows])


REFERENCE_BATCH = 4  # sequences to a forward pass: what fits beside the engine


def plain_logits(family, cfg: dict, weights: dict, prompts, forced, prefixes, lossy=None):
    """The family's reference logits at the same positions: a full forward
    pass over prompt + forced tokens, a few sequences at a time. ``lossy``
    (the control only) names the type the reference rounds to."""
    steps = len(forced[0])
    out = []
    for lo in range(0, len(prompts), REFERENCE_BATCH):
        group = list(range(lo, min(lo + REFERENCE_BATCH, len(prompts))))
        t_max = max(len(prompts[i]) for i in group) + steps
        tokens = np.zeros((len(group), t_max), np.int32)
        positions = []
        for row, i in enumerate(group):
            p = prompts[i]
            tokens[row, : len(p) + steps] = p + forced[i]
            positions.append([n_p - 1 for n_p in prefixes[i]]
                             + list(range(len(p) - 1, len(p) + steps)))
        out.append(family.reference_logits(
            cfg, weights, tokens, np.asarray(positions, np.int32), lossy=lossy))
    return np.concatenate(out)


def relative_errors(got: np.ndarray, want: np.ndarray):
    """Per-row relative error of centred rows; ``[B, R]``."""
    g = got - got.mean(axis=-1, keepdims=True)
    w = want - want.mean(axis=-1, keepdims=True)
    return np.linalg.norm(g - w, axis=-1) / np.linalg.norm(w, axis=-1)


def _rms(x) -> float:
    return float(np.sqrt(np.mean(np.square(x))))


# -- routes ---------------------------------------------------------------


def greedy_gap(row: np.ndarray, token: int) -> float:
    """How far the token's logit lies under the row's largest, in standard
    deviations of the row: 0 for the argmax."""
    return float((row.max() - row[token]) / row.std())


def nucleus_excess(row: np.ndarray, token: int, temp: float, topp: float):
    """(how far past ``topp`` the probability ahead of the token reaches, the
    nucleus's share of the vocabulary). The nucleus as the published sampler
    defines it: tokens by falling probability at the temperature, kept up to
    and including the one that crosses ``topp``; 0 for a token inside it."""
    order = np.argsort(-row, kind="stable")
    z = row[order].astype(np.float64) / max(float(temp), 1e-6)
    p = np.exp(z - z[0])
    p /= p.sum()
    ahead = np.cumsum(p) - p
    rank = int(np.nonzero(order == token)[0][0])
    return max(0.0, float(ahead[rank] - topp)), float(np.mean(ahead < topp))


def split_admission(prompt, buckets):
    """The two chunks a prompt is admitted in: cut at the largest prefill
    bucket not above half of it (at half of it where none is)."""
    half = len(prompt) // 2
    cut = max([b for b in buckets if b <= half], default=max(1, half))
    return prompt[:cut], prompt[cut:]


def route_check(family, cfg: dict, engine, prompts, seed: int,
                fault: str | None = None) -> dict:
    """The pipelined and the fused programs against the synchronous ones
    (module docstring, **Routes**), on lanes of its own filling: the logits'
    part is done and on the host. No lane is stepped twice over one position.
    ``fault`` (the control only) = "swap_admits": the chain admits each
    admission's prompt into the other's lane, as a splice into the wrong lane
    would; the twins keep their own."""
    spec = cfg["correctness"]
    temp = float(spec["sampler"]["temperature"])
    topp = float(spec["sampler"]["top_p"])
    ia, ib = (int(i) for i in spec["route_admits"])
    n, seq_len = engine.n_lanes, engine.config.seq_len
    if engine.pipeline_depth < 2:
        raise ValueError("the route check dispatches two steps deep")
    if fault not in (None, "swap_admits"):
        raise ValueError(f"unknown fault {fault!r}")
    k = min(len(prompts), (n - 4) // 2)
    if k < 2:
        raise ValueError(
            "the route check needs two chain lanes, two admitted lanes and a "
            f"twin of each: 8 lanes, have {n}")
    chain = list(range(k))
    lane_a, lane_b, twin_a, twin_b = range(2 * k, 2 * k + 4)
    # chain lane -> the twin that replays it
    twin = dict(zip(chain + [lane_a, lane_b], list(range(k, 2 * k)) + [twin_a, twin_b]))
    rng = np.random.default_rng([int(seed), 5])
    seeds = rng.integers(1, 2**31 - 1, size=n).astype(np.uint32)
    topps = np.full(n, topp, np.float32)
    lane_temp = np.zeros(n, np.float32)
    lane_temp[chain[1::2]] = temp  # odd chain lanes sample
    lane_temp[lane_b] = temp       # as does the second admission
    feed0 = np.zeros(n, np.int32)
    feed0[chain] = rng.integers(2, cfg["vocab_size"], size=k)
    for x, y in twin.items():
        # a twin draws as its chain lane does: by sampler seed and position
        seeds[y], lane_temp[y] = seeds[x], lane_temp[x]

    def temps_of(lanes):
        t = np.zeros(n, np.float32)
        t[lanes] = lane_temp[lanes]
        return t

    def carried(lanes):  # live lanes read the device's carry, the rest park
        p = np.full(n, seq_len, np.int32)
        p[lanes] = -1
        return p

    # both lanes of a pair are filled before the chain, so that a step that
    # disturbs a parked lane shows in the replay. b's twin is filled in the
    # chunks its prompt is admitted in: another bucket is another program,
    # which rounds other bits (on the chip a prompt prefilled whole and in
    # two chunks differ by 6-8 % of the largest key in the last layers)
    for i, x in enumerate(chain):
        engine.prefill(x, prompts[i])
        engine.prefill(twin[x], prompts[i])
    row_a = np.asarray(engine.prefill(twin_a, prompts[ia])[0], np.float32)
    first, rest = split_admission(prompts[ib], engine.prefill_buckets)
    engine.prefill(twin_b, first)
    row_b = np.asarray(engine.prefill(twin_b, rest, start_pos=len(first))[0], np.float32)

    chunk_a, chunk_b = prompts[ia], prompts[ib]
    if fault == "swap_admits":
        chunk_a, chunk_b = chunk_b, chunk_a
    first, rest = split_admission(chunk_b, engine.prefill_buckets)
    with_a, with_ab = chain + [lane_a], chain + [lane_a, lane_b]

    def admit(live, lane, chunk, start):  # the admitting lane parks in the decode half
        engine.decode_prefill_fused(
            carried(live), temps_of(live), topps, seeds, p_lane=lane, chunk=chunk,
            p_start=start, p_temp=float(lane_temp[lane]), p_topp=topp, p_seed=int(seeds[lane]))

    # the chain, two steps deep as the scheduler keeps it: a reseeded step, an
    # admission whole, one in two chunks, a chained step from the carry
    pos0 = np.full(n, seq_len, np.int32)
    pos0[chain] = [len(prompts[i]) for i in range(k)]
    engine.decode_pipelined(pos0, temps_of(chain), topps, seeds, tokens=feed0)
    admit(chain, lane_a, chunk_a, 0)
    outs = [engine.pipeline_consume()]
    admit(with_a, lane_b, first, 0)
    outs.append(engine.pipeline_consume())
    admit(with_a, lane_b, rest, len(first))
    outs.append(engine.pipeline_consume())
    engine.decode_pipelined(carried(with_ab), temps_of(with_ab), topps, seeds)
    outs += [engine.pipeline_consume(), engine.pipeline_consume()]
    engine.pipeline_flush()
    stepped = [chain, chain, with_a, with_a, with_ab]  # the lanes each step decoded

    gaps, excesses, shares, mismatches = [0.0], [0.0], [], 0

    def check_token(row, greedy_tok, sampled_tok, t):
        gaps.append(greedy_gap(row, int(greedy_tok)))
        if t > 0.0:
            excess, share = nucleus_excess(row, int(sampled_tok), t, topp)
            excesses.append(excess)
            shares.append(share)

    def chosen(out, lane, column=None):  # column n: a fused step's boundary pair
        return out[int(lane_temp[lane] != 0.0)][lane if column is None else column]

    # the replay, on the twins alone: one synchronous step for each step of
    # the chain, fed with the tokens the chain chose, at the positions the
    # carry has to hold; the chain's lanes park
    feed = np.zeros(n, np.int32)
    pos = np.full(n, seq_len, np.int32)
    for x in chain:
        feed[twin[x]], pos[twin[x]] = feed0[x], pos0[x]
    for out, lanes in zip(outs, stepped):
        temps = temps_of([twin[x] for x in lanes])
        logits, greedy, sampled = engine.decode(
            feed, pos, temps, topps, seeds, want_logits=True)
        logits = np.asarray(logits, np.float32)
        for x in lanes:
            y = twin[x]
            check_token(logits[y], out[0][x], out[1][x], float(temps[y]))
            mismatches += int(out[0][x] != greedy[y]) + int(out[1][x] != sampled[y])
            feed[y] = chosen(out, x)
            pos[y] += 1
        if out is outs[1]:    # a's twin joins with a's boundary token
            feed[twin_a], pos[twin_a] = chosen(out, lane_a, n), len(prompts[ia])
        elif out is outs[3]:  # b's with the token b's last chunk ended on;
            # outs[2] carries the first chunk's boundary slot, which is no token
            feed[twin_b], pos[twin_b] = chosen(out, lane_b, n), len(prompts[ib])
    # the admissions: boundary tokens against engine.prefill's logits for the
    # same prompt
    check_token(row_a, outs[1][0][n], outs[1][1][n], float(lane_temp[lane_a]))
    check_token(row_b, outs[3][0][n], outs[3][1][n], float(lane_temp[lane_b]))
    # every pair has absorbed the same tokens, pos[twin] of them: the whole of
    # their state is compared (reported under the name it had when keys and
    # values were the only kind)
    state = [family.lane_state_rel_err(engine, x, y, int(pos[y])) for x, y in twin.items()]
    return {
        "route_greedy_gap": max(gaps),
        "route_nucleus_excess": max(excesses),
        "route_kv_rel_err": None if None in state else max(state),
        # pair by pair: the chain's lanes, then the admission whole, then the
        # one in two chunks
        "route_state_rel_errs": state,
        "route_tokens": len(gaps) + len(excesses) - 2,
        "route_token_mismatches": mismatches,
        "nucleus_share_of_vocab": float(np.mean(shares)) if shares else None,
    }


def compare(family, cfg: dict, weights: dict, engine, seed: int, fault: str | None = None,
            keep_rows: bool = False) -> dict:
    """The numbers compared, each beside its limit, and the verdict.
    ``engine`` may be a type's name instead: the control then puts the
    reference, rounded to that type, in the program's place (logits only)."""
    prompts, forced = sample_sequences(cfg, seed)
    prefixes = [prefix_lengths(cfg, len(p)) for p in prompts]
    n_pre = len(prefixes[0]) + 1
    t0 = time.monotonic()
    if isinstance(engine, str):
        got = plain_logits(family, cfg, weights, prompts, forced, prefixes, lossy=engine)
    else:
        got = engine_logits(engine, prompts, forced, prefixes)
    t1 = time.monotonic()
    routes = ({} if isinstance(engine, str)
              else route_check(family, cfg, engine, prompts, seed, fault))
    t2 = time.monotonic()
    want = plain_logits(family, cfg, weights, prompts, forced, prefixes)
    t3 = time.monotonic()
    err = relative_errors(got, want)
    out = {
        "prefill_rel_err": _rms(err[:, :n_pre]),
        "decode_rel_err": _rms(err[:, n_pre:]),
        **routes,
        "rows": int(err.size),
        "largest_row": float(err.max()),
        # what the comparison cost
        "compare_seconds": {"logits": round(t1 - t0, 3), "routes": round(t2 - t1, 3),
                            "reference": round(t3 - t2, 3)},
    }
    if keep_rows:
        out["row_errors"] = [[float(x) for x in r] for r in err]
    limits = cfg["correctness"]["limits"]
    ok = bool(np.isfinite(err).all())
    for key, limit in limits.items():
        out[key + "_limit"] = float(limit)
        if out.get(key) is not None:
            ok = ok and out[key] <= float(limit)
    out["ok"] = ok
    return out


def describe(compared: dict) -> str:
    """Each number compared beside its limit, for the run's log."""
    def fmt(x):
        return "not compared" if x is None else f"{x:.6g}"

    return ", ".join(
        f"{key[:-6]} {fmt(compared.get(key[:-6]))} (limit {compared[key]:g})"
        for key in compared if key.endswith("_limit")
    )
