"""How `correct` is decided: the served tokens against the plain reference.

After the window has closed and the engine is freed, a sample of the
requests the engine finished (drawn from the seed, the longest of them
always in it) is run through the reference forward once per request, over
its prompt followed by its served tokens.  At every served position the
number compared is the gap by which the served token's reference logit
lies below the reference's best logit, in units of that logit row's
standard deviation.  `max_gap_sigma` is the widest such gap in the sample.

The control puts the reference in the program's place at the next lower
precision (float8 e4m3 operands, `quant=True`): at the same positions, the
gap of the token the control ranks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax.numpy as jnp
import numpy as np

from .loadgen import seed_rng


def sample(finished: Sequence, k: int, seed: int) -> List:
    """k finished requests: the longest (prompt + output) and k - 1 more
    drawn from the seed."""
    reqs = sorted(finished, key=lambda r: r.uid)
    if not reqs:
        return []
    longest = max(reqs, key=lambda r: (len(r.prompt) + len(r.out_tokens),
                                       -r.uid))
    rest = [r for r in reqs if r is not longest]
    rng = seed_rng(seed + 1)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def _gaps(ref, chosen) -> np.ndarray:
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return np.asarray((best - got) / jnp.std(ref, axis=-1))


def compare(ref_mod, cfg: dict, params, reqs: Sequence,
            control: bool = False) -> Dict[str, float]:
    """Widest gap (sigma) of the served tokens, and with `control` of the
    float8 control's first choices, over every served position of `reqs`."""
    worst = worst_ctl = 0.0
    n_pos = 0
    for r in reqs:
        prompt, out = list(r.prompt), list(r.out_tokens)
        seq = np.asarray(prompt + out[:-1], np.int32)
        pos = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
        ref = ref_mod.logits_at(cfg, params, seq, pos)
        served = jnp.asarray(np.asarray(out, np.int32))
        worst = max(worst, float(_gaps(ref, served).max()))
        if control:
            ctl = ref_mod.logits_at(cfg, params, seq, pos, quant=True)
            first = jnp.argmax(ctl, axis=-1).astype(jnp.int32)
            worst_ctl = max(worst_ctl, float(_gaps(ref, first).max()))
        n_pos += len(pos)
    out = {"max_gap_sigma": worst, "positions": n_pos}
    if control:
        out["control_max_gap_sigma"] = worst_ctl
    return out
