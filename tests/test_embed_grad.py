"""The token lookup with a gradient rule of its own
(``ops/row_sums.py::take_rows``): value and gradient against ``jnp.take``'s,
with the cotangent's rows summed at their ids by the Pallas kernel (in the
interpreter here) and by ``jnp.take``'s own transpose, which a CPU takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bluefog_tpu.ops import row_sums


def rand(shape, seed, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


@pytest.fixture
def in_the_interpreter(monkeypatch):
    """The kernel form, in tiles of 64 rows at 128 columns."""
    monkeypatch.setattr(row_sums, "_lookup_form",
                        lambda v, d: "vmem_interpret")
    monkeypatch.setattr(row_sums, "_VMEM_SUMS", 64 * 128 * 4)


def value_and_gradient(lookup, table, ids, dtype, seed=7):
    probe = rand(ids.shape + table.shape[1:], seed, dtype)

    def total(table):
        x = lookup(table)
        return (x * probe).astype(jnp.float32).sum(), x

    (_, x), g = jax.value_and_grad(total, has_aux=True)(table)
    return x, g, probe


IDS = {
    "uniform": lambda v: jax.random.randint(jax.random.PRNGKey(3), (2, 96),
                                            0, v),
    "all-equal": lambda v: jnp.full((2, 96), v // 3, jnp.int32),
    "all-distinct": lambda v: jax.random.permutation(
        jax.random.PRNGKey(4), v)[:192].reshape(2, 96).astype(jnp.int32),
    "first-and-last-row": lambda v: jnp.tile(
        jnp.array([0, v - 1, v - 1, 0, 5, v - 2], jnp.int32), 32).reshape(
            2, 96),
    "from-the-end-and-outside": lambda v: jnp.tile(
        jnp.array([-1, -v, v, 3 * v, 7, -v - 1], jnp.int32), 32).reshape(
            2, 96),
    "one-id": lambda v: jnp.array([v - 1], jnp.int32),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("ids", sorted(IDS))
@pytest.mark.parametrize("v", [200, 256], ids=["short-last-tile",
                                               "whole-tiles"])
def test_value_and_gradient_are_jnp_takes(v, ids, dtype, in_the_interpreter):
    """A table of 200 rows in tiles of 64 (the last one 8 rows) and one of
    256 (four whole tiles): the value is ``jnp.take``'s to the bit, ids from
    the end and outside the table included; the gradient is its transpose's,
    to the order of an f32 sum where the lookup is f32, and the f32 sum of
    the bf16 rows where XLA's scatter would add them in bf16."""
    assert row_sums.sums_tile(v, 128) == 64
    table, at = rand((v, 128), 0), IDS[ids](v)
    x, g, probe = value_and_gradient(
        lambda t: row_sums.take_rows(t, at, dtype), table, at, dtype)
    want_x, want_g, _ = value_and_gradient(
        lambda t: jnp.take(t.astype(dtype), at, axis=0), table, at, dtype)
    assert x.dtype == dtype and g.dtype == table.dtype
    np.testing.assert_array_equal(np.asarray(x, np.float32),
                                  np.asarray(want_x, np.float32))
    # the same rows summed in f32, the rows a fill answered left out
    wrapped = jnp.where(at < 0, at + v, at).reshape(-1)
    inside = (wrapped >= 0) & (wrapped < v)
    in_f32 = jnp.zeros((v, 128), jnp.float32).at[
        jnp.where(inside, wrapped, 0)].add(jnp.where(
            inside[:, None], probe.reshape(-1, 128).astype(jnp.float32), 0))
    np.testing.assert_allclose(g, in_f32, rtol=1e-6, atol=1e-6)
    loose = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2 * float(jnp.abs(want_g).max()))
    np.testing.assert_allclose(g, want_g, **loose)


@pytest.mark.parametrize("v,d,tile,tiles", [
    (18992, 2560, 1024, 19),        # smallthinker: the last tile 560 rows
    (25008, 2560, 1024, 25),        # phi4flash: 432
    (16160, 2048, 1024, 16),        # joyai: 800
    (50304, 768, 2048, 25),         # gpt2-small: 1,152
    (16384, 2560, 1024, 16),        # the expert layer's tokens: whole tiles
    (96, 64, 96, 1),                # a table that fits: one tile
    (6 * 1031, 2560, None, 0),      # rows no multiple of 8, and too many
    (4096, 1 << 20, None, 0)])      # no eight rows this wide fit
def test_the_tile_follows_rows_and_columns_alone(v, d, tile, tiles):
    """The most rows of a power of two within 10.5 MB of f32 sums, whatever
    divides the table: the last tile is short."""
    assert row_sums.sums_tile(v, d) == tile
    if tile is not None:
        assert tile * d * 4 <= row_sums._VMEM_SUMS
        assert -(-v // tile) == tiles and (v - (tiles - 1) * tile) % 8 == 0


def test_the_kernel_sums_onto_what_a_short_last_tile_held(monkeypatch):
    """``add_rows_at`` onto sums that are there (the expert layer's form) in
    tiles of 64 with a last one of 8: the rows of every tile, the short one
    too, are added to what it held, and a tile no row meets stays."""
    monkeypatch.setattr(row_sums, "_VMEM_SUMS", 64 * 128 * 4)
    acc = rand((200, 128), 0)
    index = jnp.sort(jnp.concatenate([
        jax.random.randint(jax.random.PRNGKey(1), (120,), 0, 64),
        jnp.full((8,), 199), jnp.arange(192, 200)])).astype(jnp.int32)
    rows = rand((136, 128), 2, jnp.bfloat16)
    got = row_sums.add_rows_at(acc, index, jnp.int32(136), (rows,), None,
                               name="sums", interpret=True)
    want = acc.at[index].add(rows.astype(jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[64:192], acc[64:192])


def model_gradients(sizes, form, monkeypatch):
    from bluefog_tpu.models.transformer import (
        GPTConfig, TransformerLM, next_token_loss)

    monkeypatch.setattr(row_sums, "_lookup_form", lambda v, d: form)
    monkeypatch.setattr(row_sums, "_VMEM_SUMS", 32 * 64 * 4)
    cfg = GPTConfig(vocab_size=96, hidden_size=64, num_layers=1, num_heads=4,
                    max_position=64, dtype=jnp.float32, **sizes)
    model = TransformerLM(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5),
                                (2, 16 + 1 + cfg.mtp_depth), 0, 96)
    state = jax.jit(lambda key: model.init(
        key, tokens[:, :16],
        **({"next_tokens": tokens[:, 1:17]} if cfg.mtp_depth else {})))(
            jax.random.PRNGKey(0))
    params = state.pop("params")
    return jax.jit(jax.grad(lambda p: next_token_loss(
        model, p, state, tokens, mtp_weight=0.1)))(params)


@pytest.mark.parametrize("sizes", [
    {}, {"mtp_depth": 1}, {"tie_head": True}],
    ids=["learned-positions", "mtp", "tied-head"])
def test_a_model_s_gradients_are_the_same_under_both_forms(sizes,
                                                           monkeypatch):
    """GPT-2's layout (learned positions beside the token table), a model
    with the MTP module (two lookups of one table: the gradient is both
    sums) and one with a tied head (the head's part and the lookup's): every
    leaf's gradient through the kernel is the one through ``jnp.take``'s
    transpose."""
    got = model_gradients(sizes, "vmem_interpret", monkeypatch)
    assert row_sums.sums_tile(96, 64) == 32
    want = model_gradients(sizes, "scatter", monkeypatch)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7),
        got, want)
    assert float(jnp.abs(got["tok"]["embedding"]).max()) > 0


@pytest.mark.parametrize("form,share", [("vmem_interpret", 1.0),
                                        ("scatter", 0.0)])
def test_the_counters_say_which_form_summed_the_rows(form, share,
                                                     monkeypatch):
    """192 ids looked up, gradient taken once: ``bf_embed_rows_total``
    counts them under both forms, ``bf_embed_vmem_rows_total`` those the
    kernel summed."""
    from bluefog_tpu.metrics import registry

    monkeypatch.setattr(row_sums, "_lookup_form", lambda v, d: form)
    table, at = rand((200, 128), 0), IDS["uniform"](200)
    registry.metrics_stop()
    registry._STOPPED = False
    reg = registry.metrics_start()
    try:
        jax.block_until_ready(jax.jit(jax.grad(lambda t: row_sums.take_rows(
            t, at, jnp.float32).sum()))(table))
        jax.effects_barrier()
        snap = reg.snapshot()
    finally:
        registry.metrics_stop()
        registry._STOPPED = False
    assert snap["bf_embed_rows_total"] == 192
    assert snap.get("bf_embed_vmem_rows_total", 0) == share * 192


@pytest.mark.parametrize("v,d,form", [
    (25008, 2560, "vmem"), (18992, 2560, "vmem"),     # phi4flash, smallthinker
    (16160, 2048, "vmem"), (50304, 768, "scatter"),   # joyai, gpt2-small
    (6 * 1031, 2560, "scatter"),                      # no tile fits
    (129280, 7168, "vmem")])
def test_on_a_tpu_the_form_follows_the_table_s_shape_alone(v, d, form,
                                                           monkeypatch):
    """The kernel where a row's f32 sums are at least ``_KERNEL_ROW_BYTES``
    and a tile of them fits; XLA's scatter-add for narrower rows, which it
    moves as fast (PERF.md section 6, PR 37)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert row_sums._lookup_form(v, d) == form


def test_off_the_tpu_the_rule_is_jnp_takes_own_transpose():
    """No TPU here: the form is the scatter-add at every shape, and the
    gradient's program holds no kernel and no sort."""
    assert row_sums._lookup_form(25008, 2560) == "scatter"
    table, at = rand((200, 128), 0), IDS["uniform"](200)
    text = jax.jit(jax.grad(lambda t, at: row_sums.take_rows(
        t, at, jnp.bfloat16).astype(jnp.float32).sum())).lower(
            table, at).compile().as_text()
    assert " scatter(" in text and " sort(" not in text


def test_the_benchmark_script_runs_both_forms_on_a_tiny_table(tmp_path):
    """``benchmarks/embed_grad_bench.py``, which times the lookup's gradient
    as XLA's scatter-add and through the kernel on the chip at the four
    cells' tables (PERF.md section 6, PR 37), at its tiny shape: both forms
    run (the kernel in the interpreter) and agree to bf16 rounding, the
    program's own rule is back in place afterwards, and a CPU run names
    itself and gives no device time."""
    import json
    import os
    import sys

    from tests._util import REPO
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import embed_grad_bench

    rule = row_sums._lookup_form
    out = embed_grad_bench.main(
        ["--shapes", "tiny", "--out", str(tmp_path / "bench.json")])
    assert row_sums._lookup_form is rule
    assert out["platform"] == "cpu"
    for form in ("scatter", "vmem"):
        entry = out[f"tiny.{form}"]
        assert entry["ids"] == 256 and entry["table"] == [200, 128]
        assert len(entry["wall_ms"]) == 3 and entry["device_ms"] is None
    assert 0 < out["tiny.forms_apart"] < 0.1
    with open(tmp_path / "bench.json") as f:
        assert json.load(f) == json.loads(json.dumps(out))
