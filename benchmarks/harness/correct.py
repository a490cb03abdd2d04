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
dispatches it (a reseeded pipelined step, two fused steps that admit sample
prompts into spare lanes, a chained step that decodes those lanes from the
carry), with half the lanes greedy and half sampled, and then replayed step by
step through `engine.decode` with the tokens the chain chose. Compared: each
greedy token of the chain against the replay's logits (how far under the row's
largest logit it lies, in standard deviations of the row: 0 unless two logits
tie); each sampled token against the nucleus computed here on the host from
those logits (how far past ``top_p`` the probability before it reaches: 0
inside the nucleus); the boundary token of each fused admission likewise
against the logits `engine.prefill` gave for the same prompt; and the per-lane
state the fused step wrote against what `engine.prefill` wrote.

The model is reached through the configuration's family module alone
(`cells.load_family`): its plain reference and its comparison of two lanes'
state. Nothing here knows an architecture.
"""

from __future__ import annotations

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


def route_check(family, cfg: dict, engine, prompts, forced, prompt_rows, seed: int,
                fault: str | None = None) -> dict:
    """The pipelined and the fused programs against the synchronous ones, on
    the lanes `engine_logits` left filled (module docstring, **Routes**).
    ``prompt_rows[i]``: the logits `engine.prefill` gave at prompt i's last
    position. ``fault`` (the control only) = "swap_admits": the two fused
    steps admit each other's prompt, as a splice into the wrong lane would."""
    spec = cfg["correctness"]
    temp = float(spec["sampler"]["temperature"])
    topp = float(spec["sampler"]["top_p"])
    ia, ib = (int(i) for i in spec["route_admits"])
    n, seq_len = engine.n_lanes, engine.config.seq_len
    if engine.pipeline_depth < 2:
        raise ValueError("the route check dispatches two steps deep")
    steps = len(forced[0])
    live = list(range(len(prompts)))
    lane_a, lane_b = n - 2, n - 1
    rng = np.random.default_rng([int(seed), 5])
    seeds = rng.integers(1, 2**31 - 1, size=n).astype(np.uint32)
    topps = np.full(n, topp, np.float32)
    lane_temp = np.zeros(n, np.float32)
    lane_temp[[i for i in live if i % 2]] = temp  # odd sample lanes sample
    lane_temp[lane_b] = temp                      # as does the second admission
    feed0 = np.zeros(n, np.int32)
    feed0[live] = rng.integers(2, cfg["vocab_size"], size=len(live))
    pos0 = np.full(n, seq_len, np.int32)
    pos0[live] = [len(p) + steps for p in prompts]

    def temps_of(lanes):
        t = np.zeros(n, np.float32)
        t[lanes] = lane_temp[lanes]
        return t

    def carried(lanes):  # live lanes read the device's carry, the rest park
        p = np.full(n, seq_len, np.int32)
        p[lanes] = -1
        return p

    chunk_a, chunk_b = prompts[ia], prompts[ib]
    if fault == "swap_admits":
        chunk_a, chunk_b = chunk_b, chunk_a
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    with_a, with_ab = live + [lane_a], live + [lane_a, lane_b]
    # the chain, two steps deep as the scheduler keeps it
    engine.decode_pipelined(pos0, temps_of(live), topps, seeds, tokens=feed0)
    engine.decode_prefill_fused(
        carried(live), temps_of(live), topps, seeds, p_lane=lane_a,
        chunk=chunk_a, p_start=0, p_temp=0.0, p_topp=topp, p_seed=int(seeds[lane_a]))
    out_a = engine.pipeline_consume()
    engine.decode_prefill_fused(
        carried(with_a), temps_of(with_a), topps, seeds, p_lane=lane_b,
        chunk=chunk_b, p_start=0, p_temp=temp, p_topp=topp, p_seed=int(seeds[lane_b]))
    out_b = engine.pipeline_consume()
    engine.decode_pipelined(carried(with_ab), temps_of(with_ab), topps, seeds)
    out_c = engine.pipeline_consume()
    out_d = engine.pipeline_consume()
    engine.pipeline_flush()

    gaps, excesses, shares, mismatches = [0.0], [0.0], [], 0

    def check_token(row, greedy_tok, sampled_tok, t):
        gaps.append(greedy_gap(row, int(greedy_tok)))
        if t > 0.0:
            excess, share = nucleus_excess(row, int(sampled_tok), t, topp)
            excesses.append(excess)
            shares.append(share)

    def chosen(out, lanes):
        greedy, sampled = out
        return np.where(lane_temp[lanes] == 0.0, greedy[lanes], sampled[lanes])

    # the replay: one synchronous step for each step of the chain, fed with
    # the tokens the chain chose, at the positions the carry has to hold
    feed, pos = feed0.copy(), pos0.copy()
    for out, lanes in ((out_a, live), (out_b, live), (out_c, with_a), (out_d, with_ab)):
        temps = temps_of(lanes)
        logits, greedy, sampled = engine.decode(
            feed, pos, temps, topps, seeds, want_logits=True)
        logits = np.asarray(logits, np.float32)
        for i in lanes:
            check_token(logits[i], out[0][i], out[1][i], float(temps[i]))
            mismatches += int(out[0][i] != greedy[i]) + int(out[1][i] != sampled[i])
        feed[lanes] = chosen(out, lanes)
        pos[lanes] += 1
        if out is out_b:    # lane_a joins with its boundary token (greedy)
            feed[lane_a], pos[lane_a] = out[0][n], len(prompts[ia])
        elif out is out_c:  # lane_b with its own (sampled)
            feed[lane_b], pos[lane_b] = out[1][n], len(prompts[ib])
    # the admissions: boundary tokens against engine.prefill's logits for the
    # same prompt, and the per-lane state written (reported under the name it
    # had when keys and values were the only kind)
    check_token(prompt_rows[ia], out_b[0][n], out_b[1][n], 0.0)
    check_token(prompt_rows[ib], out_c[0][n], out_c[1][n], temp)
    kv = [family.lane_state_rel_err(engine, lane_a, ia, len(prompts[ia])),
          family.lane_state_rel_err(engine, lane_b, ib, len(prompts[ib]))]
    return {
        "route_greedy_gap": max(gaps),
        "route_nucleus_excess": max(excesses),
        "route_kv_rel_err": None if None in kv else max(kv),
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
    routes = {}
    if isinstance(engine, str):
        got = plain_logits(family, cfg, weights, prompts, forced, prefixes, lossy=engine)
    else:
        got = engine_logits(engine, prompts, forced, prefixes)
        routes = route_check(family, cfg, engine, prompts, forced, got[:, n_pre - 1], seed, fault)
    want = plain_logits(family, cfg, weights, prompts, forced, prefixes)
    err = relative_errors(got, want)
    out = {
        "prefill_rel_err": _rms(err[:, :n_pre]),
        "decode_rel_err": _rms(err[:, n_pre:]),
        **routes,
        "rows": int(err.size),
        "largest_row": float(err.max()),
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
