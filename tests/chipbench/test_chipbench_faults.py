"""A run with the timed path broken underneath reads ``correct`` false, once
for each fault a one-chip serving cell can have.  (The exchange between
chips does not exist on one chip.)"""
import dataclasses

import jax.numpy as jnp
import pytest
from conftest import PAGED, cpu_chip

import repro.models
from chipbench import run


def state_unchanged(fn):
    def step(params, cache, *rest):
        return fn(params, cache, *rest)[0], cache
    return step


def half_batch_left_out(fn):
    def step(*args):
        logits, cache = fn(*args)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:logits.shape[0] - half]), cache
    return step


def token_altered(fn):
    def step(*args):
        logits, cache = fn(*args)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


FAULTS = {
    "decode_state_unchanged": ("decode_step", state_unchanged),
    "prefill_state_unchanged": ("decode_chunk", state_unchanged),
    "half_batch_left_out": ("decode_step", half_batch_left_out),
    "token_altered": ("decode_step", token_altered),
}


# tiny.docs keeps every lane busy (a backlog of two requests per lane);
# in the open-loop cells the sample's spread over lanes has to find the
# lanes a fault confines itself to; tiny.chat is the dense layout
@pytest.mark.parametrize("cell,fault", [("tiny.docs", f) for f in sorted(FAULTS)]
                         + [("tiny.chat", "decode_state_unchanged"),
                            ("tiny.chat", "token_altered"),
                            ("tiny.chat", "half_batch_left_out"),
                            ("tiny.prefix", "half_batch_left_out")])
def test_fault_reads_incorrect(tiny_root, monkeypatch, cell, fault):
    field, wrap = FAULTS[fault]
    build = repro.models.build_model

    def broken(cfg):
        model = build(cfg)
        name = field + ("_paged" if cell in PAGED else "")
        return dataclasses.replace(model, **{name: wrap(getattr(model, name))})

    monkeypatch.setattr(repro.models, "build_model", broken)
    args = run.parse(["--workload", cell, "--seed", "23", "--seconds", "2"])
    res = run.measure(args, root=tiny_root, chip_fn=cpu_chip)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
