"""The float32 reference against the program's own full forward at a tiny
size, the weights it regenerates against those the program serves, and
both against the readings the dense harness gave before families were
files (``data/pins.json``)."""
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DATA, REPO

from chipbench import reference, spec, weights

DENSE = spec.family(REPO, {"family": "dense_gqa"}, "test")
RULE = DENSE.leaf_std
PINS = json.loads((DATA / "pins.json").read_text())


def tiny_model(dtype=None):
    from repro.models import build_model

    conf = json.loads((DATA / "configs" / "tiny.json").read_text())
    cfg = DENSE.program_config(dict(conf, torch_dtype=dtype) if dtype else conf)
    model = build_model(cfg)
    return cfg, model, jax.eval_shape(model.init, jax.random.key(0))


@pytest.fixture(scope="module")
def tiny():
    return tiny_model("float32")


def test_weights_regenerate_layer_by_layer(tiny):
    cfg, _, shapes = tiny
    seed = 2**32 + 3
    params = weights.make(shapes, seed, RULE)
    for i in range(cfg.n_layers):
        one = weights.layer_from_key(shapes, weights.key_for(seed), i, RULE)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(params["layers"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[i]))
    np.testing.assert_array_equal(np.asarray(weights.top(shapes, seed, "lm_head", RULE)),
                                  np.asarray(params["lm_head"]))
    other = weights.make(shapes, 3, RULE)  # same low 32 bits, other seed
    assert not np.array_equal(np.asarray(other["embed"]), np.asarray(params["embed"]))
    wq = np.asarray(params["layers"]["attn"]["wq"])
    assert wq.std() == pytest.approx(1 / np.sqrt(cfg.d_model), rel=0.5)
    scale = np.asarray(params["layers"]["attn_norm"]["scale"])
    assert 0.74 < scale.min() and scale.max() < 1.26


def test_reference_matches_the_program_prefill(tiny):
    cfg, model, shapes = tiny
    seed = 11
    params = weights.make(shapes, seed, RULE)
    toks = list(np.random.default_rng(0).integers(1, cfg.vocab_size, 37))
    want, _ = model.prefill(params, {"tokens": jnp.asarray([toks], jnp.int32)})
    (h,) = reference.hidden(shapes, seed, DENSE, DENSE.arch(cfg), [toks], 64)
    head = reference.head_weights(shapes, seed, DENSE)
    got = np.asarray(reference._logits(h, head, cfg.vocab_size, False)[len(toks) - 1])
    np.testing.assert_allclose(got, np.asarray(want[0]), atol=2e-4, rtol=2e-4)
    # served as the next token, the reference's best has gap 0, its worst a wide one
    prompt = toks[:-1]
    best, worst = int(np.argmax(got)), int(np.argmin(got))
    assert reference.served_gaps(h, head, prompt, [toks[-1], best], cfg.vocab_size)[1] == 0.0
    assert reference.served_gaps(h, head, prompt, [toks[-1], worst], cfg.vocab_size)[1] > 1.0


def test_fp8_control_departs_from_the_reference(tiny):
    cfg, _, shapes = tiny
    arch = DENSE.arch(cfg)
    toks = list(np.random.default_rng(1).integers(1, cfg.vocab_size, 64))
    (h,) = reference.hidden(shapes, 4, DENSE, arch, [toks], 96)
    (c,) = reference.hidden(shapes, 4, DENSE, arch, [toks], 96, fp8=True)
    head = reference.head_weights(shapes, 4, DENSE)
    gaps = reference.choice_gaps(h, c, head, toks[:1], toks[1:] + [0], cfg.vocab_size)
    assert len(gaps) == 64 and gaps.max() > 0 and gaps.min() >= 0


def _sha(a) -> str:
    a = np.asarray(a)
    if a.dtype == jnp.bfloat16:
        a = a.view(np.uint16)
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def test_tiny_weights_reference_and_control_are_the_pinned_ones():
    """The served (bf16) tiny model: every leaf's bits, and the float32
    reference's and the fp8 control's logits, as the dense harness gave them
    before families were files."""
    pin = PINS["tiny"]
    cfg, _, shapes = tiny_model()
    seed, toks = pin["seed"], pin["tokens"]
    flat, _ = jax.tree_util.tree_flatten_with_path(weights.make(shapes, seed, RULE))
    assert {"/".join(p.key for p in path): _sha(v) for path, v in flat} == \
        pin["weights_sha256_16"]
    arch = DENSE.arch(cfg)
    (h,) = reference.hidden(shapes, seed, DENSE, arch, [toks], pin["length"])
    (c,) = reference.hidden(shapes, seed, DENSE, arch, [toks], pin["length"], fp8=True)
    head = reference.head_weights(shapes, seed, DENSE)
    n = len(toks)
    # float32 on the CPU: equal to rounding on any CPU, bit for bit on one
    for hs, fp8, want in ((h, False, pin["reference"]), (c, True, pin["control"])):
        got = np.asarray(reference._logits(hs, head, cfg.vocab_size, fp8))[:n]
        assert got.argmax(-1).tolist() == want["argmax"]
        np.testing.assert_allclose(got.max(-1), want["max"], rtol=1e-5)
    gaps = reference.choice_gaps(h, c, head, toks[:1], toks[1:] + [0], cfg.vocab_size)
    np.testing.assert_allclose(gaps, pin["choice_gaps"], rtol=1e-4, atol=1e-5)
    served = reference.served_gaps(h, head, toks[:10], toks[10:], cfg.vocab_size)
    np.testing.assert_allclose(served, pin["served_gaps_from_10"], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", sorted(PINS["rules"]))
def test_real_weight_rules_are_the_pinned_ones(name):
    """At full size the weights are fixed by each leaf's kind and power-of-two
    scale (the draw is shared and integer-exact): the family gives every
    leaf of the real configurations the rule the dense harness gave it."""
    from repro.models import build_model

    conf = json.loads((REPO / "chipbench" / "configs" / f"{name}.json").read_text())
    family = spec.family(REPO, conf, name)
    shapes = jax.eval_shape(build_model(family.program_config(conf)).init, jax.random.key(0))
    got = {}
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        p = tuple(k.key for k in path)
        kind, std = family.leaf_std(p, s.shape[1:] if p[0] == "layers" else s.shape)
        got["/".join(p)] = [kind, weights._scale_exp(std)]
    assert got == PINS["rules"][name]
