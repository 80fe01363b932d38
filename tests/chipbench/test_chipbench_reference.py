"""The float32 reference against the program's own full forward at a tiny
size, and the weights it regenerates against those the program serves."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DATA

from chipbench import harness, reference, weights


@pytest.fixture(scope="module")
def tiny():
    from repro.models import build_model

    conf = json.loads((DATA / "configs" / "tiny.json").read_text())
    cfg = harness.model_config(dict(conf, torch_dtype="float32"))
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    return cfg, model, shapes


def test_weights_regenerate_layer_by_layer(tiny):
    cfg, _, shapes = tiny
    seed = 2**32 + 3
    params = weights.make(shapes, seed, cfg.n_layers)
    for i in range(cfg.n_layers):
        one = weights.layer_from_key(shapes, weights.key_for(seed), i)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(params["layers"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[i]))
    np.testing.assert_array_equal(np.asarray(weights.top(shapes, seed, "lm_head")),
                                  np.asarray(params["lm_head"]))
    other = weights.make(shapes, 3, cfg.n_layers)  # same low 32 bits, other seed
    assert not np.array_equal(np.asarray(other["embed"]), np.asarray(params["embed"]))
    wq = np.asarray(params["layers"]["attn"]["wq"])
    assert wq.std() == pytest.approx(1 / np.sqrt(cfg.d_model), rel=0.5)
    scale = np.asarray(params["layers"]["attn_norm"]["scale"])
    assert 0.74 < scale.min() and scale.max() < 1.26


def test_reference_matches_the_program_prefill(tiny):
    cfg, model, shapes = tiny
    seed = 11
    params = weights.make(shapes, seed, cfg.n_layers)
    toks = list(np.random.default_rng(0).integers(1, cfg.vocab_size, 37))
    want, _ = model.prefill(params, {"tokens": jnp.asarray([toks], jnp.int32)})
    arch = harness.reference_arch(cfg)
    (h,) = reference.hidden(shapes, seed, arch, [toks], 64)
    head = reference.head_weights(shapes, seed)
    got = np.asarray(reference._logits(h, head, cfg.vocab_size, False)[len(toks) - 1])
    np.testing.assert_allclose(got, np.asarray(want[0]), atol=2e-4, rtol=2e-4)
    # served as the next token, the reference's best has gap 0, its worst a wide one
    prompt = toks[:-1]
    best, worst = int(np.argmax(got)), int(np.argmin(got))
    assert reference.served_gaps(h, head, prompt, [toks[-1], best], cfg.vocab_size)[1] == 0.0
    assert reference.served_gaps(h, head, prompt, [toks[-1], worst], cfg.vocab_size)[1] > 1.0


def test_fp8_control_departs_from_the_reference(tiny):
    cfg, _, shapes = tiny
    arch = harness.reference_arch(cfg)
    toks = list(np.random.default_rng(1).integers(1, cfg.vocab_size, 64))
    (h,) = reference.hidden(shapes, 4, arch, [toks], 96)
    (c,) = reference.hidden(shapes, 4, arch, [toks], 96, fp8=True)
    head = reference.head_weights(shapes, 4)
    gaps = reference.choice_gaps(h, c, head, toks[:1], toks[1:] + [0], cfg.vocab_size)
    assert len(gaps) == 64 and gaps.max() > 0 and gaps.min() >= 0
