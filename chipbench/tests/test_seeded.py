"""What PR 34 gave the two sparse cells, on the CPU: the shared helper
rescales exactly the leaves a configuration names, the stream of each
family's small model tells tokens apart further under the kept weights than
under normal(0.02) throughout, and the warming rate. What ``correct`` holds
their first optimizer steps to: ``test_first_steps.py``."""

import copy
import math
import os

import pytest

from chipbench import cells, seeded
from chipbench.jobs import trainstep

HERE = os.path.dirname(os.path.abspath(__file__))
FAMILIES = {
    "glm4_moe_lite": ("tiny-glm.train", "rehearsal_glm"),
    "smallthinker": ("tiny-smallthinker.train", "rehearsal_smallthinker"),
}
CELLS = {
    "glm4_moe_lite": "glm-4.7-flash.train-4k",
    "smallthinker": "smallthinker-21b-a3b.train-16k",
}
# a small model wide enough for the collapse to show: a branch's gain is
# 0.02 x sqrt(width), so a toy of width 32 hardly has one
WIDER = {
    "glm4_moe_lite": {
        "hidden_size": 256, "intermediate_size": 1024,
        "moe_intermediate_size": 192, "num_attention_heads": 4,
        "q_lora_rank": 96, "kv_lora_rank": 64, "qk_nope_head_dim": 48,
        "qk_rope_head_dim": 16, "v_head_dim": 64, "num_hidden_layers": 5,
        "vocab_size": 2048,
    },
    "smallthinker": {
        "hidden_size": 256, "num_attention_heads": 8, "head_dim": 64,
        "num_key_value_heads": 2, "moe_ffn_hidden_size": 96,
        "vocab_size": 2048, "sliding_window_size": 128,
    },
}
SEQ = 768  # the longest a CPU test affords here


def tiny(family_name):
    name, directory = FAMILIES[family_name]
    cell = cells.load_cell(name, os.path.join(HERE, directory))
    family = cells.load_module("families", family_name, cell.roots)
    return cell, family


def leaves(tree):
    import jax

    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def own_init(family, config, job):
    """The model's own ``init``, as the family calls it, with no rescaling."""
    unscaled = copy.deepcopy(config)
    unscaled["initializer"] = {
        **config["initializer"], "residual_outputs": [], "embedding": [],
    }
    return family.task(unscaled, job).init_fn


@pytest.mark.parametrize("family_name", sorted(FAMILIES))
def test_the_helper_rescales_the_named_leaves_and_no_other(family_name):
    import jax
    import numpy as np

    cell, family = tiny(family_name)
    job = cell.workload["job_params"]
    rng = jax.random.PRNGKey(7)
    kept = leaves(family.task(cell.config, job).init_fn(rng)[0])
    own = leaves(own_init(family, cell.config, job)(rng)[0])
    factors = seeded.leaf_factors(cell.config["initializer"])
    assert kept.keys() == own.keys()
    touched = set()
    for path, leaf in own.items():
        names = [n for n in factors if f"['{n}']" in path]
        want = leaf * factors[names[0]] if names else leaf
        np.testing.assert_array_equal(np.asarray(kept[path]), np.asarray(want))
        touched.update(names)
    assert touched == set(factors)


@pytest.mark.parametrize("family_name", sorted(CELLS))
def test_the_cells_name_residual_outputs_at_the_published_depth(family_name):
    config = cells.load_cell(CELLS[family_name]).config
    block = config["initializer"]
    depth = config["num_hidden_layers_published"]
    assert block["range"] == 0.02 and block["residual_layers"] == depth
    factors = seeded.leaf_factors(block)
    assert set(factors) == set(block["residual_outputs"]) | {"embed_tokens"}
    for name in block["residual_outputs"]:
        assert factors[name] == pytest.approx(1 / math.sqrt(2 * depth))
    assert block["embedding_std"] == 1.0  # unit variance: 50 x the range
    assert factors["embed_tokens"] == pytest.approx(50.0)


def test_a_name_on_no_leaf_is_an_error():
    import jax.numpy as jnp

    tree = {"a": {"o_proj": {"kernel": jnp.ones((2, 2))}}, "b": jnp.ones(3)}
    out = seeded.rescale(tree, {"o_proj": 0.5})
    assert float(out["a"]["o_proj"]["kernel"][0, 0]) == 0.5
    assert float(out["b"][0]) == 1.0
    with pytest.raises(ValueError, match="down_proj"):
        seeded.rescale(tree, {"o_proj": 0.5, "down_proj": 0.5})


def mean_vector_share(x):
    """The collapse's own measure (PERF.md section 6): the norm of the mean
    row over the root mean square of the rows' norms. 1 where every token's
    row is one vector, 1 / sqrt(rows) where they are independent."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32).reshape(-1, x.shape[-1])
    return float(
        jnp.linalg.norm(jnp.mean(x, 0)) / jnp.sqrt(jnp.mean(jnp.sum(x * x, -1)))
    )


def last_layer_input(family_name, family, config, job, params, tokens):
    """The residual stream as the last layer gets it: the layer before's
    output, captured from the model's own forward pass (einsum attention)."""
    import importlib

    from pytorch_distributedtraining_tpu.models.gpt2 import default_attention

    cfg = family.model_config(config, {**job, "remat": False})
    models = importlib.import_module(type(cfg).__module__)
    if family_name == "glm4_moe_lite":
        model = models.Glm4MoeLite(cfg, default_attention, interpret=True)
    else:
        model = models.SmallThinker(cfg, models.banded_attention, interpret=True)
    _, got = model.apply(
        {"params": params}, tokens, mutable=["intermediates"],
        capture_intermediates=lambda m, _: (m.name or "").startswith("layers_"),
    )
    before_last = f"layers_{cfg.num_hidden_layers - 2}"
    return got["intermediates"][before_last]["__call__"][0]


@pytest.mark.parametrize("family_name", sorted(FAMILIES))
def test_the_stream_tells_tokens_apart_further_under_the_kept_weights(
    family_name,
):
    import jax
    import jax.numpy as jnp

    cell, family = tiny(family_name)
    config = {**cell.config, **WIDER[family_name]}
    # the depth the cells rescale by, whatever the toy's own
    config["initializer"] = dict(
        cells.load_cell(CELLS[family_name]).config["initializer"]
    )
    job = {**cell.workload["job_params"], "batch": 1, "seq": SEQ,
           "compute_dtype": "float32"}
    rng = jax.random.PRNGKey(11)
    tokens = jnp.asarray(
        next(seeded.even_batches(11, 1, SEQ, config["vocab_size"]))[0]
    )
    share = {}
    for name, init_fn in (
        ("kept", family.task(config, job).init_fn),
        ("normal(0.02) throughout", own_init(family, config, job)),
    ):
        x = last_layer_input(
            family_name, family, config, job, init_fn(rng)[0], tokens
        )
        share[name] = mean_vector_share(x)
    floor = 1 / math.sqrt(SEQ)
    assert floor * 0.5 < share["kept"] < share["normal(0.02) throughout"]
    # and not by a hair (0.05 against 0.41 and 0.09 against 0.77 here)
    assert share["kept"] < share["normal(0.02) throughout"] / 3, share


@pytest.mark.parametrize(
    "step,want", [(0, 0.0), (1000, 1.5e-4), (2000, 3e-4), (3000, 3e-4)]
)
def test_the_rate_warms_up_linearly_and_then_stays(step, want):
    schedule = trainstep.rate(
        {"lr": 3e-4, "warmup_steps": 2000, "clip_grad_norm": 1.0}
    )
    assert float(schedule(step)) == pytest.approx(want, abs=1e-10)


def test_the_glm_cell_warms_up_and_the_other_cells_keep_a_constant_rate():
    glm = cells.load_cell(CELLS["glm4_moe_lite"]).workload["job_params"]
    assert glm["optimizer"] == {
        "lr": 0.0003, "warmup_steps": 2000, "clip_grad_norm": 1.0,
    }
    assert "zipf_exponent" not in glm
    for name in ("smallthinker-21b-a3b.train-16k", "gpt2-125m.train",
                 "gpt2-xl.zero3-4chip"):
        params = cells.load_cell(name).workload["job_params"]["optimizer"]
        assert trainstep.rate(params) == params["lr"]


def test_a_warming_optimizer_moves_nothing_at_step_0_and_something_after():
    import jax.numpy as jnp
    import optax

    tx = trainstep.optimizer({"lr": 3e-4, "warmup_steps": 2000,
                              "clip_grad_norm": 1.0})
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 0.5)}
    state = tx.init(params)
    updates, state = tx.update(grads, state, params)
    assert float(jnp.abs(updates["w"]).max()) == 0.0
    updates, state = tx.update(grads, state, params)
    moved = optax.apply_updates(params, updates)
    assert 0 < float(jnp.abs(moved["w"] - 1).max()) < 3e-4 / 1000
