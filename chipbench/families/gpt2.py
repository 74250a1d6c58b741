"""GPT-2 (``models/gpt2.py``): what a training job needs of it, built from a
configuration file that holds the published ``config.json`` keys."""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

TRAIN_MULT = 3.0  # forward + backward = 3 x the forward's matmul FLOPs


def model_config(config: dict, job: dict):
    """The program's ``GPT2Config`` for the published sizes; ``remat`` and
    ``scan_layers`` are the job's choice, not the model's."""
    import jax.numpy as jnp

    from pytorch_distributedtraining_tpu.models import GPT2Config

    if config.get("n_inner") not in (None, 4 * config["n_embd"]):
        raise ValueError("models/gpt2.py has a 4x MLP only")
    return GPT2Config(
        vocab_size=config["vocab_size"], n_positions=config["n_positions"],
        n_embd=config["n_embd"], n_layer=config["n_layer"],
        n_head=config["n_head"], mlp_ratio=4, dropout=0.0,
        dtype=jnp.dtype(job.get("compute_dtype", "bfloat16")),
        tie_word_embeddings=config.get("tie_word_embeddings", True),
        remat=job.get("remat", False),
        scan_layers=job.get("scan_layers", False),
    )


def train_flops_per_token(config: dict, seq: int) -> float:
    """Matmul FLOPs the forward and backward passes require per token (2mnk
    a matmul, backward twice the forward; copied from
    ``observe.goodput.transformer_fwd_flops``). Convention, stated: the two
    attention matmuls are counted over the full T x T square, not the causal
    half; the output head is counted, embedding lookups, LayerNorms, softmax
    and the optimizer are not; recomputation is not."""
    d, layers = config["n_embd"], config["n_layer"]
    per_layer = (
        2 * 4 * d * d  # qkv + output projection
        + 2 * 2 * seq * d  # qk^T and att.v over the full square
        + 2 * 2 * 4 * d * d  # mlp up + down
    )
    return TRAIN_MULT * (layers * per_layer + 2 * d * config["vocab_size"])


@dataclasses.dataclass
class Task:
    init_fn: object
    loss_fn: object
    units_per_step: int
    flops_per_step: float
    batches: object  # seed -> iterator of host (tokens, targets)
    reference: object  # (params, batch) -> {"loss", "grad_norm"}


def zipf_batches(seed: int, batch: int, seq: int, vocab: int, exponent: float):
    """Endless host batches of token ids drawn by rank from a Zipf law
    (p(rank r) ~ r^-exponent over the whole vocabulary), ranks mapped to ids
    by a seeded permutation: a unigram distribution for the loss to learn."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks**-exponent)
    cdf /= cdf[-1]
    ids = rng.permutation(vocab).astype(np.int32)
    while True:
        draw = np.searchsorted(cdf, rng.random((batch, seq + 1)))
        tok = ids[np.minimum(draw, vocab - 1)]
        yield tok[:, :-1], tok[:, 1:]


def task(config: dict, job: dict) -> Task:
    import jax
    import jax.numpy as jnp

    from chipbench.reference import gpt2 as reference
    from pytorch_distributedtraining_tpu.models import GPT2, cross_entropy_loss

    cfg = model_config(config, job)
    model = GPT2(cfg)
    batch, seq = job["batch"], job["seq"]

    def init_fn(rng):
        return model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"], {}

    def loss_fn(params, batch, rng, model_state):
        tokens, targets = batch
        logits = model.apply({"params": params}, tokens)
        return cross_entropy_loss(logits, targets), {}

    ref = jax.jit(functools.partial(
        reference.loss_and_grad_norm, n_layer=cfg.n_layer, n_head=cfg.n_head,
        chunks=job["reference_chunks"],
    ))

    def run_reference(params, first_batch):
        loss, gnorm = ref(params, *first_batch)
        return {"loss": float(loss), "grad_norm": float(gnorm)}

    return Task(
        init_fn=init_fn, loss_fn=loss_fn,
        units_per_step=batch * seq,
        flops_per_step=train_flops_per_token(config, seq) * batch * seq,
        batches=lambda seed: zipf_batches(
            seed, batch, seq, cfg.vocab_size, job["zipf_exponent"]
        ),
        reference=run_reference,
    )
