"""Whether what the timed path served is correct.

Once the window has closed, and the requests whose stream crossed a
replica move have finished, a sample drawn from the seed of the requests
the service finished (the longest of them, and every one that crossed a
move, always in it) is fed to the plain reference: each request's prompt,
right-padded to its bucket with token 0 as the engine pads it, followed by
its served tokens.  For every served token the reference gives the gap by
which that token's logit lies below the reference's best logit at that
position; with greedy decoding a correct engine serves the best token up
to rounding near ties.  The widest gap over the sample is compared with
the cell's limit (``bench/limits/<workload>.json``).  A finished request
that served fewer or more tokens than it asked for is wrong too.  The
control (the reference in float8) is judged by the same rule, on the
tokens it puts first at each position of the same sequences.
"""

from __future__ import annotations

import numpy as np


def crossed(r, moves) -> bool:
    """The request's stream was in flight when a move's command came."""
    return any(_across(r, m) for m in moves)


def _across(r, m) -> bool:
    """Tokens on both sides of the move: before its command and after its
    resume returned (or none yet, the request still in flight)."""
    return bool(r.times) and r.times[0] < m.t_cmd and (
        r.done_t is None or r.times[-1] > m.t_resumed)


def sample(records: list, moves: list, seed: int, want_tokens: int,
           max_requests: int) -> list:
    done = [r for r in records if r.index >= 0 and r.done_t is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens) + r.prompt_len,
                                       r.index))
    # every move's streams in turn, so each move is in a small sample
    per_move = [[r for r in done if _across(r, m)] for m in moves]
    cross = [lst[k] for k in range(max(map(len, per_move), default=0))
             for lst in per_move if k < len(lst)]
    must = {longest.rid} | {r.rid for r in cross}
    rng = np.random.default_rng([seed, 4])
    rest = [done[i] for i in rng.permutation(len(done))]
    out, seen, n_tok = [], set(), 0
    for r in [longest] + cross + rest:
        if r.rid in seen or len(out) >= max_requests:
            continue
        if r.rid not in must and n_tok >= want_tokens:
            break
        out.append(r)
        seen.add(r.rid)
        n_tok += len(r.tokens)
    return out


def arrays(sample_: list, rows: int, length: int) -> tuple:
    """Token and target matrices for the reference: row ``b`` holds
    request ``b``'s padded prompt and served tokens; ``targets[b, i]`` is
    the token served after position ``i``."""
    tokens = np.zeros((rows, length), np.int32)
    targets = np.full((rows, length), -1, np.int32)
    for b, r in enumerate(sample_):
        prompt = np.asarray(r.req.prompt, np.int32)[:r.bucket]
        seq = np.concatenate([np.pad(prompt, (0, r.bucket - len(prompt))),
                              np.asarray(r.tokens[:-1], np.int32)])
        tokens[b, :len(seq)] = seq
        targets[b, r.bucket - 1:r.bucket - 1 + len(r.tokens)] = r.tokens
    return tokens, targets


def judge(ref_out: dict, sample_: list, limits: dict) -> dict:
    """The numbers compared, each beside its limit, and the verdict.

    With the control in ``ref_out`` the control stands in the program's
    place: its own choices at each position are judged by the same rule,
    and what the program served is shown beside them as ``served_gap``."""
    lim = limits["logit_gap"]["limit"]
    control = "control" in ref_out
    gaps = ref_out["control" if control else "served"][:len(sample_)]
    per_req = np.nanmax(gaps, axis=1) if gaps.size else np.zeros(0)
    widest = float(np.max(per_req)) if per_req.size else float("nan")
    short = 0 if control else sum(1 for r in sample_
                                  if len(r.tokens) != r.max_new)
    checks = {
        "logit_gap": {"value": widest, "limit": lim},
        "requests_checked": {"value": len(sample_), "limit": 1},
        "tokens_checked": {"value": int(sum(len(r.tokens)
                                            for r in sample_)),
                           "limit": 1},
        "wrong_length": {"value": short, "limit": 0},
    }
    if control and sample_:
        checks["served_gap"] = {
            "value": float(np.nanmax(ref_out["served"][:len(sample_)])),
            "limit": lim}
    failed = int(np.sum(per_req > lim)) + short
    ok = (len(sample_) > 0 and widest <= lim and short == 0)
    return {"correct": bool(ok), "failed": failed, "checks": checks}
