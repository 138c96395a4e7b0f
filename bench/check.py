"""Decide ``correct``: served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample of
the requests the window finished — drawn from the seed, with the longest
among them — is run once through the reference (``bench/reference.py``),
teacher-forced on each prompt and its served tokens. Each served token is
greedy, so its reference logit should be the reference's best; the gap
between the two is what rounding in the program's bf16 path leaves. The
mean gap over the sample is compared with its limit in
``bench/checks/<cell>.json``. The widest gap is printed beside it as a
reading, not compared: on the chip the float8 control's widest gap is
only about twice the sound program's (PERF.md), so no limit between the
two would hold.

The control (``control=True``) puts, at each of the same positions, the
token that the float8 reference puts first where the served token was,
and judges those tokens by the same numbers and limits: a sound limit
makes it come out not correct.
"""
from __future__ import annotations

import numpy as np

from . import reference

__all__ = ["sample", "compare", "judge"]


def sample(done: list, count: int, seed: int) -> list:
    """The longest finished request and ``count - 1`` others, drawn from
    the seed (by request index, so the draw does not follow timing)."""
    if not done:
        return []
    done = sorted(done, key=lambda t: t.spec.index)
    longest = max(done, key=lambda t: (len(t.req.out), -t.spec.index))
    rest = [t for t in done if t is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 4])
    pick = rng.permutation(len(rest))[: max(count - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def judge(gaps: np.ndarray, limits: dict) -> dict:
    """The numbers compared, each beside its limit, and whether all hold."""
    checks = {"mean_gap": {"value": float(gaps.mean()) if gaps.size else
                           float("nan"), "limit": limits["mean_gap"]}}
    ok = bool(gaps.size) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return {"checks": checks, "correct": ok}


def compare(model: dict, weight_seed: int, picked: list, limits: dict,
            pad_to: int, control: bool = False) -> dict:
    """Gaps of the picked requests' served tokens, judged against
    ``limits``; with ``control`` the float8 control's tokens are judged
    in their place, and the served tokens' gaps are kept as a reading."""
    served, ctrl = [], []
    for t in picked:
        out = reference.token_gaps(model, weight_seed, t.spec.prompt,
                                   np.asarray(t.req.out), control=control,
                                   pad_to=pad_to)
        served.append(out["gaps"])
        if control:
            ctrl.append(out["control_gaps"])
    g = np.concatenate(served) if served else np.zeros(0)
    res = {"tokens": int(g.size), "requests": len(picked),
           "max_gap": float(g.max()) if g.size else float("nan"),
           "mean_gap": float(g.mean()) if g.size else float("nan")}
    judged = np.concatenate(ctrl) if control else g
    res.update(judge(judged, limits))
    if control:
        res["control_max_gap"] = float(judged.max()) if judged.size else float("nan")
    return res
