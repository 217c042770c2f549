"""The data side of the block-diffusion objective (models/llama.py:block_diffusion_loss): a
batch is token ids AND the realised noise. What a user's collate calls on the sequences it
drew, with the run's seeded generator; the benchmark's driver calls the same function."""
from typing import Dict

import numpy as np

# the schedule's floor: a sequence is noised at p = (1 - EPS) t + EPS, t ~ U[0, 1) (the
# masked-diffusion convention of LLaDA and BD3-LMs that SDAR's training code follows), so
# that the loss's weight 1 / p stays finite
EPS = 1e-3


def block_diffusion_noise(rng: np.random.Generator, tokens: np.ndarray, eps: float = EPS) -> Dict[str, np.ndarray]:
    """tokens [B, L] -> the batch `llama.loss_fn` takes under cfg.diffusion_block: {"tokens",
    "masked" [B, L] bool: the positions the noised copy hides, each drawn at the sequence's
    rate, "p_mask" [B] float32: that rate, (1 - eps) t + eps with one t ~ U[0, 1) a
    sequence}. The linear schedule; the model's mask token and block length are the
    configuration's, not the batch's."""
    tokens = np.asarray(tokens)
    p = (1.0 - eps) * rng.random(tokens.shape[0]) + eps
    masked = rng.random(tokens.shape) < p[:, None]
    return {"tokens": tokens, "masked": masked, "p_mask": p.astype(np.float32)}
