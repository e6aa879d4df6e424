"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed, goes through the
float32 reference (``reference.py``) once: each prompt with the tokens it
was served. At every served position the reference's logits give the gap
by which the served token's logit lies below the reference's best. The
number compared is the widest such gap; a greedy token that the program
chose at a bfloat16 near-tie reads a small gap, a wrong token a large one.

The sample holds at least one request of every (batch bucket, replica)
that served in the window, so prefill and fused decode are checked at
every bucket and on every chip used, and the gaps are read on the prompt
each output was returned to, so an output given to the wrong request
reads as wrong tokens.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

import arch
import reference


def sample(groups: Sequence, n_min: int, seed: int) -> np.ndarray:
    """Indices of served requests to check: one of each group, then more
    drawn at random until ``n_min``. ``groups[i]`` is request i's group,
    or None where it was not served."""
    rng = np.random.default_rng([seed, 4])
    served = [i for i, g in enumerate(groups) if g is not None]
    by_group: Dict = {}
    for i in rng.permutation(served):
        by_group.setdefault(groups[i], []).append(int(i))
    picked = [members[0] for members in by_group.values()]
    rest = [i for members in by_group.values() for i in members[1:]]
    rng.shuffle(rest)
    picked += rest[:max(0, n_min - len(picked))]
    return np.sort(np.asarray(picked, dtype=np.int64))


def rows_per_block(cfg: dict, seq: int, budget_bytes: float = 1.5e9) -> int:
    """Rows whose widest float32 activation over ``seq`` positions, as the
    architecture's module counts it, fits ``budget``."""
    wide = arch.load(cfg).widest(cfg, seq)
    return max(1, int(budget_bytes // (4 * seq * wide)))


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: the reference's best logit minus its logit of
    ``tokens``. ref_logits (rows, n, vocab); tokens (rows, n)."""
    best = ref_logits.max(-1)
    chosen = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - chosen


def teacher_forced(prompts: np.ndarray, served: np.ndarray) -> np.ndarray:
    """Each prompt followed by its served tokens but the last: position
    ``prompt_len - 1 + j`` of the result predicts served token ``j``."""
    return np.concatenate([prompts, served[:, :-1]], axis=1)


def widest_gap(cfg: dict, key_seed: int, prompts: np.ndarray,
               served: np.ndarray, control: bool = False) -> Dict[str, float]:
    """The widest gap of the served tokens; with ``control`` also that of
    the tokens a float8 forward puts first at the same positions."""
    seq = prompts.shape[1] + served.shape[1] - 1
    tokens = teacher_forced(prompts, served)
    block = rows_per_block(cfg, seq)
    first = prompts.shape[1] - 1
    ref = reference.forward(cfg, key_seed, tokens, first, rows_per_block=block)
    out = {"gap": float(gaps(ref, served).max()),
           "tokens": int(served.size)}
    if control:
        low = reference.forward(cfg, key_seed, tokens, first, control=True,
                                rows_per_block=block)
        out["control_gap"] = float(gaps(ref, low.argmax(-1)).max())
    return out


def verdict(numbers: List[dict]) -> bool:
    """Every number compared within its limit."""
    return all(n["value"] <= n["limit"] for n in numbers)
