"""Whether the timed path served correct answers.

After the window, a sample of the finished requests, drawn from the
seed, with the longest prompt among them and at least one escalated
request, is scored by the float32 reference over each prompt and the
tokens actually served.  Two numbers are compared, each with the cell's
limit:

* ``logit_gap`` — over every served token (both tiers' streams of an
  escalated request): how far the served token's reference logit lies
  below the reference's best logit at that position.  Greedy decoding
  serves the argmax, so only rounding can open a gap.
* ``conf_rel_err`` — the confidences the gate read against the
  reference's max softmax probability, relative: per token on the tier
  that gave the final answer, and the sequence mean that the cheap
  tier's gate compared with δ on escalated requests.

The control (``control.py``) reads the same two numbers with the
reference in fp8 put in the program's place.
"""
from __future__ import annotations

import numpy as np

from bench.harness import reference

NUMBERS = ("logit_gap", "conf_rel_err")


def sample(requests, n: int, seed: int) -> list:
    """``n`` finished requests: the longest prompt, the longest escalated
    one, then the rest drawn from the seed."""
    done = sorted((r for r in requests if r.state.name == "DONE"),
                  key=lambda r: r.rid)
    if not done:
        return []
    pick = [max(done, key=lambda r: (r.prompt_tokens, -r.rid))]
    esc = [r for r in done if r.tier > 0 and r is not pick[0]]
    if esc and pick[0].tier == 0:
        pick.append(max(esc, key=lambda r: (r.prompt_tokens, -r.rid)))
    rest = [r for r in done if r not in pick]
    rng = np.random.default_rng([seed, 2])
    extra = max(n - len(pick), 0)
    if rest and extra:
        idx = rng.choice(len(rest), size=min(extra, len(rest)), replace=False)
        pick += [rest[i] for i in sorted(idx)]
    return pick


def readings(reqs, params, cfgs) -> dict:
    """The compared numbers over ``reqs`` (each with the served streams
    ``tokens_by_tier``, the final tier's ``token_conf`` and the gate's
    ``seq_conf_by_tier``)."""
    gap, err, tokens = 0.0, 0.0, 0
    for req in reqs:
        for tier, toks in enumerate(req.tokens_by_tier):
            ref = reference.score(params[tier], cfgs[tier], req.prompt, toks)
            gap = max(gap, float(np.max(ref["best"] - ref["at"])))
            tokens += len(toks)
            if tier == req.tier:          # per-token confidences kept
                got = np.asarray(req.token_conf, np.float64)
                err = max(err, float(np.max(np.abs(got - ref["conf"])
                                            / ref["conf"])))
            else:                          # the gate's sequence mean
                want = float(np.mean(ref["conf"]))
                err = max(err, abs(req.seq_conf_by_tier[tier] - want) / want)
    return {"logit_gap": gap, "conf_rel_err": err, "tokens": tokens,
            "requests": len(reqs)}


def control_readings(reqs, params, cfgs) -> dict:
    """The same numbers with the fp8 reference in the program's place: at
    each position the token fp8 puts first, and fp8's confidence."""
    gap, err, tokens = 0.0, 0.0, 0
    for req in reqs:
        for tier, toks in enumerate(req.tokens_by_tier):
            c = reference.control_score(params[tier], cfgs[tier],
                                        req.prompt, toks)
            gap = max(gap, float(np.max(c["best"] - c["at"])))
            err = max(err, float(np.max(np.abs(c["conf_low"] - c["conf"])
                                        / c["conf"])))
            tokens += len(toks)
    return {"logit_gap": gap, "conf_rel_err": err, "tokens": tokens,
            "requests": len(reqs)}


def judge(numbers: dict, limits: dict) -> bool:
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in NUMBERS)
