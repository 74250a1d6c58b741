"""What "from ``--seed``" means in the cells whose routers read the residual
stream: token ids with no hot id, and weights whose stream still tells one
token from the next when it reaches the last layer. One place for the
families that need either (``families/glm4_moe_lite.py``,
``families/smallthinker.py``); every number comes from the configuration
file's ``initializer`` block, none is fixed here.

Why a family cannot leave a model's own ``init`` as it is (PERF.md, section
6, PR 32 and PR 34): drawn normal(0, 0.02) throughout, each residual branch
writes several times what the embedding put into the stream, attention hands
the stream's common part on whole and averages a token's own part away, and
a few layers in the stream is one vector for every token. A router that
reads it picks the same experts for every token: which of them the chip
holds is then a lottery of the seed, and the lottery is the step's time."""

from __future__ import annotations

import math

import numpy as np


def even_batches(seed: int, batch: int, seq: int, vocab: int):
    """Endless host batches of token ids drawn evenly and independently
    over the vocabulary held, every step a fresh draw from the seed's
    stream: with random weights a router is nearly a function of the token
    id, and under even ids the rows that land on the held experts do not
    depend on which ids a seed made hot."""
    rng = np.random.default_rng(seed)
    while True:
        tok = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        yield tok[:, :-1], tok[:, 1:]


def leaf_factors(initializer: dict) -> dict:
    """``{leaf name: factor}`` that takes a tree drawn normal(0, ``range``)
    throughout to what a configuration's ``initializer`` block states:

    - ``residual_outputs``, the projections that write into the residual
      stream, at ``range / sqrt(2 x residual_layers)`` (GPT-2, Radford et
      al. 2019, section 2.3; Megatron-LM's ``scaled_init_method_normal``):
      ``residual_layers`` is the PUBLISHED depth, a cut model is its
      leading layers;
    - ``embedding``, the embedding rows, at ``embedding_std`` (1:
      ``torch.nn.Embedding``'s default, which T5 trains from; Tensor
      Programs V's width-independent input scale)."""
    residual = 1.0 / math.sqrt(2.0 * initializer["residual_layers"])
    embedding = initializer["embedding_std"] / initializer["range"]
    return {
        **{name: residual for name in initializer["residual_outputs"]},
        **{name: embedding for name in initializer["embedding"]},
    }


def rescale(params, factors: dict):
    """``params`` with every leaf that has one of ``factors``' names on its
    path multiplied by that name's factor, every other leaf as it was. A
    normal draw times a constant is a normal draw of that much the standard
    deviation, so the model's own ``init`` stays the only source of
    randomness. A name that is on no leaf's path is an error: a renamed
    module must not quietly leave the weights as they were."""
    import jax

    met = set()

    def scaled(path, leaf):
        keys = (getattr(entry, "key", None) for entry in path)
        name = next((key for key in keys if key in factors), None)
        if name is None:
            return leaf
        met.add(name)
        return leaf * factors[name]

    out = jax.tree_util.tree_map_with_path(scaled, params)
    if met != set(factors):
        raise ValueError(f"no leaf is named {sorted(set(factors) - met)}")
    return out
