"""What PR 48 must leave as it was: the parameter trees of the six decoder
configurations the benchmark had before it, at their published widths, and
the expert layers of the four of them that have one, at their tiny presets
(``tests/data/<family>/tiny-*.json``) on seeded inputs: value and every
gradient.  ``tests/test_mamba2_gqa_moe.py`` computes both on the tree under
test and holds them, bit for bit, to what this file recorded from PR 48's
parent commit (``accepted_trees.json``, ``accepted_expert_layers.npz``):

    python tests/data/mamba2_gqa_moe/accepted_layers.py <checkout> <out dir>

It uses nothing a checkout of either side lacks."""

import json
import os
import sys

DECODERS = ("gpt2-small", "joyai-llm-flash", "phi-4-mini-flash",
            "smallthinker-21b-a3b", "ling-3.0-flash", "lfm2-8b-a1b")
TINY = {"latent_moe": "tiny-latent-moe", "gqa_moe": "tiny-gqa-moe",
        "linear_latent_moe": "tiny-ling", "conv_gqa_moe": "tiny-lfm2"}
TRAFFIC = {"seq_len": 32, "batch": 2, "remat": False}


def trees(repo):
    """``{configuration: {leaf path: [shape, dtype]}}``, parameters and
    model state, from the benchmark's own files; shapes alone."""
    import jax
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(repo, "BENCHMARK.json"))
    out = {}
    for name in DECODERS:
        config = cells.load_json(os.path.join(
            repo, manifest.entry("configs", name)["file"]))
        family = manifest.module("families", config["family"]).build(
            config, dict(TRAFFIC, seq_len=64))
        shapes = jax.eval_shape(family.init, jax.random.PRNGKey(0))
        out[name] = {
            jax.tree_util.keystr(path): [list(leaf.shape), str(leaf.dtype)]
            for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)}
    return out


def expert_layers(repo):
    """``{family/leaf: array}``: each tiny preset's ``RoutedFFN`` on seeded
    tokens with a selection bias that is not zero: the layer's output, the
    input's gradient and every parameter's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bluefog_tpu.models.transformer import RoutedFFN
    from chipbench import cell as cells

    manifest = cells.Manifest.load(os.path.join(repo, "BENCHMARK.json"))
    out = {}
    for family_name, preset in TINY.items():
        config = cells.load_json(os.path.join(
            repo, "tests", "data", family_name, preset + ".json"))
        cfg = manifest.module("families", family_name).build(
            config, TRAFFIC).model.cfg
        layer = RoutedFFN(cfg)
        keys = jax.random.split(jax.random.PRNGKey(48), 4)
        y = jax.random.normal(keys[0], (2, 24, cfg.hidden_size), cfg.dtype)
        variables = layer.init(keys[1], y)
        state = {k: jax.tree_util.tree_map(
            lambda b: 0.3 * jax.random.normal(keys[2], b.shape), v)
            for k, v in variables.items() if k == "buffers"}
        probe = jax.random.normal(keys[3], y.shape)

        def value(params, y):
            out = layer.apply({"params": params, **state}, y)
            return jnp.sum(out.astype(jnp.float32) * probe), out

        (_, result), (d_params, d_y) = jax.value_and_grad(
            value, argnums=(0, 1), has_aux=True)(variables["params"], y)
        out[f"{family_name}/out"] = np.asarray(result)
        out[f"{family_name}/d_y"] = np.asarray(d_y)
        for path, leaf in jax.tree_util.tree_leaves_with_path(d_params):
            out[f"{family_name}/d{jax.tree_util.keystr(path)}"] = (
                np.asarray(leaf))
    return out


if __name__ == "__main__":
    import numpy as np

    checkout, target = (os.path.abspath(p) for p in sys.argv[1:3])
    sys.path.insert(0, checkout)
    os.chdir(checkout)
    with open(os.path.join(target, "accepted_trees.json"), "w") as f:
        json.dump(trees(checkout), f, indent=0, sort_keys=True)
    np.savez_compressed(os.path.join(target, "accepted_expert_layers.npz"),
                        **expert_layers(checkout))
