"""Whether the timed path's own outputs are correct.

After the window closes and the served model is freed, a sample drawn from
the seed of the requests the window finished goes through the float32
reference once each, prompt and served tokens together.  The longest is
always in it; then, in an order drawn from the seed, requests served on lanes
the sample does not cover yet, until it covers half of the lanes (a fault
confined to some lanes of the batch shows there); then others, until
``SAMPLE_TOKENS`` served tokens.  At every served
position the number read is how far the served token's reference logit lies
below the reference's best; the widest such gap over the sample is held to
the cell's limit (``chipbench/limits/<cell>.json``), set from readings of
sound runs and of the fp8 control (see PERF.md).

Greedy serving of a correct bf16 program puts first a token that the float32
reference also ranks first or within a few bf16 roundings of it; a wrong
cache position, mask, prefix page or a lower precision puts tokens first
that the reference ranks far lower.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from chipbench import reference, traffic

SAMPLE_TOKENS = 256  # served tokens the sample reaches before it stops
SAMPLE_MAX = 8  # requests at most
LANE_SHARE = 2  # the sample covers at least 1 / LANE_SHARE of the lanes
COMPLETE = "max_new_tokens"


def limits(root: Path, cell: str) -> dict:
    return json.loads((root / "chipbench" / "limits" / f"{cell}.json").read_text())


def sample(finished: list, seed: int, n_slots: int) -> list:
    """(prompt, out, lane) of the requests compared.  ``finished`` holds
    (prompt, out, max_new, finish_reason, lane); ``lane`` is None where the
    harness never saw the request on a lane."""
    done = [(p, o, lane) for p, o, want, why, lane in finished
            if why == COMPLETE and len(o) == want]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i][0]) + len(done[i][1]))
    order = [i for i in traffic.rng_for(seed, "sample").permutation(len(done)) if i != longest]
    lanes_wanted = -(-n_slots // LANE_SHARE)
    picked, lanes, tokens = [longest], {done[longest][2]}, len(done[longest][1])
    for i in order:  # first new lanes, then tokens
        if len(picked) >= SAMPLE_MAX or len(lanes - {None}) >= lanes_wanted:
            break
        if done[i][2] is not None and done[i][2] not in lanes:
            picked.append(i)
            lanes.add(done[i][2])
            tokens += len(done[i][1])
    for i in order:
        if len(picked) >= SAMPLE_MAX or tokens >= SAMPLE_TOKENS:
            break
        if i not in picked:
            picked.append(i)
            tokens += len(done[i][1])
    return [done[i] for i in picked]


def compare(shapes, seed: int, family, arch, finished: list, length: int, n_slots: int,
            control: bool = False, log=print) -> dict:
    """Readings of the sample through ``family``'s reference layer: the
    widest served gap, and with ``control`` the widest gap of the fp8
    control's choices on the same tokens.  Every sequence is padded to
    ``length`` (the engine's ``max_len``)."""
    t = time.perf_counter()
    picked = sample(finished, seed, n_slots)
    out = {"incomplete": sum(1 for _, o, want, why, _ in finished
                             if why != COMPLETE or len(o) != want),
           "sampled_tokens": sum(len(o) for _, o, _ in picked)}
    if not picked:
        out["max_logit_gap"] = float("inf")
        return out
    seqs = [p + o[:-1] for p, o, _ in picked]
    head = reference.head_weights(shapes, seed, family)
    hs = reference.hidden(shapes, seed, family, arch, seqs, length)
    out["max_logit_gap"] = max(
        float(reference.served_gaps(h, head, p, o, arch.vocab).max())
        for h, (p, o, _) in zip(hs, picked))
    if control:
        hc = reference.hidden(shapes, seed, family, arch, seqs, length, fp8=True)
        out["control_gap"] = max(
            float(reference.choice_gaps(h, c, head, p, o, arch.vocab).max())
            for h, c, (p, o, _) in zip(hs, hc, picked))
    lanes = len({lane for _, _, lane in picked} - {None})
    log(f"reference: {len(picked)} requests on {lanes} lanes, {out['sampled_tokens']} served tokens "
        f"compared in {time.perf_counter() - t:.1f}s")
    return out
