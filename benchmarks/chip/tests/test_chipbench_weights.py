"""The seeded weights have the program's parameter layout, leaf for leaf
the table the benchmark kept before it read the layout from the program
(so a seed gives the same weights as then), and the plain reference
computes what the program's own float32 forward computes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cell

from chipbench import correct, spec, weights


def _tiny():
    cell = tiny_cell()
    return cell, cell.config["arch"]


def _model(arch):
    from repro.configs.base import ArchConfig
    from repro.models import build_model
    return build_model(ArchConfig(name="tiny", **arch))


def _old_table(arch: dict) -> dict:
    """{path: shape} of the pre-LN dense tree as the benchmark wrote it out
    by hand before it read the program's (every leaf in ``arch["dtype"]``)."""
    L, d = arch["num_layers"], arch["d_model"]
    hd = arch.get("head_dim") or d // arch["num_heads"]
    qd, kvd = arch["num_heads"] * hd, arch["num_kv_heads"] * hd
    ff, V = arch["d_ff"], arch["vocab_size"]
    out = {
        "embed": (V, d),
        "pos_table": (arch["max_seq_len"], d),
        "layers/ln1/scale": (L, d), "layers/ln1/bias": (L, d),
        "layers/attn/wq": (L, d, qd), "layers/attn/wk": (L, d, kvd),
        "layers/attn/wv": (L, d, kvd), "layers/attn/wo": (L, qd, d),
        "layers/ln2/scale": (L, d), "layers/ln2/bias": (L, d),
        "layers/mlp/w_up": (L, d, ff), "layers/mlp/w_down": (L, ff, d),
        "final_norm/scale": (d,), "final_norm/bias": (d,),
    }
    if not arch.get("tie_embeddings", False):
        out["lm_head"] = (d, V)
    return out


@pytest.mark.parametrize("name", ["gpt2-1.5b", "opt-66b-s4"])
def test_declared_tree_is_the_old_table(name):
    arch = spec.load_json(spec.HERE / "configs" / f"{name}.json",
                          "configuration")["arch"]
    got = weights.declared(_model(arch))
    want = _old_table(arch)
    assert sorted(got) == sorted(want)
    for path, shape in want.items():
        assert got[path] == (shape, jnp.dtype(arch["dtype"])), path


def test_weights_are_those_of_the_old_table():
    """The old way, from the hand-written table: a seed gives the same
    bits."""
    cell, arch = _tiny()
    init = cell.config["init"]
    shp = _old_table(arch)
    paths = sorted(shp)

    def build(key):
        return {p: (weights._std(init, p) * jax.random.normal(
            jax.random.fold_in(key, i), shp[p], jnp.float32))
            .astype(arch["dtype"]) for i, p in enumerate(paths)}

    seed = 2**33 + 12345
    old = jax.device_get(jax.jit(build)(weights.root_key(seed)))
    new = jax.device_get(weights.make_params(_model(arch), init, seed))
    for p in paths:
        node = new
        for part in p.split("/"):
            node = node[part]
        assert node.dtype == old[p].dtype
        assert np.array_equal(np.asarray(node).view(np.uint16),
                              np.asarray(old[p]).view(np.uint16)), p


def test_layout_matches_the_program_init():
    cell, arch = _tiny()
    model = _model(arch)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.make_params(
        model, cell.config["init"], 0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_seed_decides_the_weights():
    cell, arch = _tiny()
    init = cell.config["init"]
    model = _model(arch)
    a = jax.device_get(weights.make_params(model, init, 5))
    b = jax.device_get(weights.make_params(model, init, 5))
    c = jax.device_get(weights.make_params(model, init, 2**32 + 5))
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(c)):
        assert np.array_equal(x, y)
        assert not np.array_equal(x, z)
    std = np.std(np.asarray(a["layers"]["attn"]["wq"], np.float32))
    assert abs(std - init["wq"]) < 0.1 * init["wq"]


def test_reference_agrees_with_the_program_in_float32():
    cell, arch = _tiny()
    arch32 = dict(arch, dtype="float32")
    model = _model(arch32)
    params = jax.device_get(weights.make_params(model, cell.config["init"],
                                                3))
    ref = spec.reference(cell.config)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, arch["vocab_size"], 30).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.prefill(params, {"tokens": prompt[None]})
                            [0][0])
    item = correct.Item(0, prompt[:-1], [int(prompt[-1]), 0])
    ids, tg, pos = correct.inputs(item, 64)
    st = ref.forward_stats(params, arch32, cell.config,
                           [ids], [tg], pad_to=64)[0]
    # position 29 is the prompt's last token: its logits predict token 0
    assert np.allclose(st["max"][29], logits.max(), rtol=1e-4, atol=1e-4)
    assert np.allclose(st["at"][29, 0], logits[0], rtol=1e-4, atol=1e-4)
    assert st["top"][29] == int(np.argmax(logits))
    assert np.allclose(st["std"][29], logits.std(), rtol=1e-4)
