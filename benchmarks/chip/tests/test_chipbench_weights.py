"""The seeded weights have the program's parameter layout, and the plain
reference computes what the program's own float32 forward computes."""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import tiny_cell

from chipbench import correct, spec, weights


def _tiny():
    cell = tiny_cell()
    return cell, cell.config["arch"]


def test_layout_matches_the_program_init():
    from repro.configs.base import ArchConfig
    from repro.models import build_model
    cell, arch = _tiny()
    model = build_model(ArchConfig(name="tiny", **arch))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.make_params(
        arch, cell.config["init"], 0))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_seed_decides_the_weights():
    cell, arch = _tiny()
    init = cell.config["init"]
    a = jax.device_get(weights.make_params(arch, init, 5))
    b = jax.device_get(weights.make_params(arch, init, 5))
    c = jax.device_get(weights.make_params(arch, init, 2**32 + 5))
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(c)):
        assert np.array_equal(x, y)
        assert not np.array_equal(x, z)
    std = np.std(np.asarray(a["layers"]["attn"]["wq"], np.float32))
    assert abs(std - init["wq"]) < 0.1 * init["wq"]


def test_reference_agrees_with_the_program_in_float32():
    from repro.configs.base import ArchConfig
    from repro.models import build_model
    cell, arch = _tiny()
    arch32 = dict(arch, dtype="float32")
    params = jax.device_get(weights.make_params(arch32, cell.config["init"],
                                                3))
    model = build_model(ArchConfig(name="tiny", **arch32))
    ref = spec.reference(cell.config)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, arch["vocab_size"], 30).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.prefill(params, {"tokens": prompt[None]})
                            [0][0])
    item = correct.Item(0, prompt[:-1], [int(prompt[-1]), 0])
    ids, tg, pos = correct.inputs(item, 64)
    st = ref.forward_stats(params, dict(arch32, layer_norm_epsilon=1e-5),
                           [ids], [tg], pad_to=64)[0]
    # position 29 is the prompt's last token: its logits predict token 0
    assert np.allclose(st["max"][29], logits.max(), rtol=1e-4, atol=1e-4)
    assert np.allclose(st["at"][29, 0], logits[0], rtol=1e-4, atol=1e-4)
    assert st["top"][29] == int(np.argmax(logits))
    assert np.allclose(st["std"][29], logits.std(), rtol=1e-4)
