"""Traffic: data files of parameters (``<mix>.json``) read by the general
generators in ``kinds/``."""

import numpy as np


def prompt_tokens(seed: int, rid: int, n: int, vocab: int) -> list:
    """Unshared random token ids of request ``rid`` (no two prompts share a
    block, so the prefix cache finds nothing)."""
    rng = np.random.default_rng([int(seed), 0x70C, int(rid)])
    return rng.integers(0, vocab, size=n).tolist()
