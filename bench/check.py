"""Whether what the timed path served is correct: a sample of the
requests it finished, drawn from the seed with the longest among them,
run once through the configuration's plain reference over each prompt
and its served tokens. The number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at
that position (greedy decoding serves the program's own best)."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import spec


def sample(records, k: int, seed: int) -> List:
    """Up to ``k`` finished requests: the longest, and the rest drawn
    from the seed."""
    done = sorted((r for r in records.values()
                   if r.status == "ok" and r.tokens), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def block(mix: dict) -> tuple:
    """The fixed (sequences, length) the reference runs a cell's sample
    in: the sample's size by the mix's longest request."""
    return int(mix["check"]["requests"]), int(mix["max_len"])


def served_gaps(config: dict, seed: int, picked, blk) -> np.ndarray:
    """The reference's gap below its best logit of every served token of
    ``picked``; a token outside the vocabulary reads infinity."""
    ref = spec.load_reference(config["reference"])
    vocab = config["vocab_size"]
    outs = [np.asarray(r.tokens, np.int64) for r in picked]
    served = np.concatenate(outs)
    if (served >= vocab).any() or (served < 0).any():
        return np.full(len(served), np.inf)
    seqs, pos = ref.served_sequences([r.prompt for r in picked], outs)
    return ref.gaps_at(config, seed, seqs, pos, [served], blk)[0]


def compare(config: dict, seed: int, records, mix: dict) -> Dict:
    blk = block(mix)
    picked = sample(records, blk[0], seed)
    if not picked:
        return {"requests": 0, "tokens": 0, "gap_max": float("inf")}
    g = served_gaps(config, seed, picked, blk)
    return {"requests": len(picked), "tokens": int(len(g)),
            "gap_max": float(g.max())}
