"""How `correct` is decided: the served tokens against the plain reference.

Once the window has closed, a sample of the greedy requests that the run
finished is drawn from the seed, the longest of them always in it, until
it holds `min_tokens` served tokens.  The reference runs once over each
prompt with its served tokens and reads, at every served position, the
gap by which the served token's logit lies below the reference's best.  A sound program serves the reference's argmax, or a token that
the reference ranks within its rounding of the best.

The number compared is the mean of those gaps over the sample.  Their
widest (`max_gap`, printed beside it) is an extreme value: it is set by
the one position where bfloat16 rounding met the nearest tie, and it
swings from seed to seed by more than the int8 control stands above it
(PERF.md).  The mean holds steady from seed to seed, and a single
served token that the reference does not rank near its best adds about
its logit spread over the sample's size to it.
"""
from __future__ import annotations

import numpy as np

from . import reference


def sample(records, seed: int, min_tokens: int):
    """Finished greedy records: the longest first, then in seeded order
    until they hold `min_tokens` served tokens (or all of them)."""
    pool = [r for r in records if r.done and not r.req.sampled]
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.task.prompt)
                                       + len(r.task.output), r.req.uid))
    rng = np.random.default_rng(seed)
    rest = [pool[i] for i in rng.permutation(len(pool))
            if pool[i] is not longest]
    out, n = [longest], len(longest.task.output)
    for r in rest:
        if n >= min_tokens:
            break
        out.append(r)
        n += len(r.task.output)
    return out


def compare(weights, model, picked, control: bool = False) -> dict:
    """The reference's reading of the picked requests' served tokens, and
    with `control` the int8 control's reading of the same positions."""
    parts = [reference.served_gaps(weights, model, r.task.prompt,
                                   np.asarray(r.task.output), control)
             for r in picked]

    def cat(key, dtype=float):
        return (np.concatenate([p[key] for p in parts]) if parts
                else np.zeros(0, dtype))

    g, h = cat("gap"), cat("hit", bool)
    out = {"tokens": int(g.size), "requests": len(picked),
           "max_gap": float(g.max()) if g.size else float("inf"),
           "mean_gap": float(g.mean()) if g.size else float("inf"),
           "argmax_share": float(h.mean()) if h.size else 0.0}
    if control and parts:
        cg = cat("control_gap")
        out.update(control_max_gap=float(cg.max()),
                   control_mean_gap=float(cg.mean()),
                   control_argmax_share=float((cg == 0).mean()))
    return out
