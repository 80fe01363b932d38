"""What the benchmark reads from the program by name: the compiled steps'
module names that ``run.PROGRAMS`` finds in a trace, and the prefill counter
behind ``prefill_useful_share.*``."""
import json
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DATA, REPO, cpu_chip

from chipbench import harness, run, spec
from repro.models import build_model
from repro.serve import EngineConfig, ServeEngine


def _module(lowered) -> str:
    return re.search(r"module @([\w.]+)", lowered.as_text()).group(1)


@pytest.mark.parametrize("conf", ["tiny", "tiny-paged"])
def test_compiled_steps_carry_the_names_the_trace_reduction_finds(conf):
    """The engine as the harness builds it: a renamed model function would
    leave ``decode_roofline``/``prefill_roofline`` with nothing to read."""
    config = json.loads((DATA / "configs" / f"{conf}.json").read_text())
    model = build_model(spec.family(REPO, config, conf).program_config(config))
    eng = ServeEngine(model, model.init(jax.random.key(0)),
                      EngineConfig(**config["engine"], guard="off"))
    bt = (jnp.asarray(eng._bt),) if eng.paged else ()
    feed = jnp.zeros((eng.cfg.n_slots, eng.cfg.prefill_chunk), jnp.int32)
    names = {"decode": _module(eng._decode.lower(eng.params, eng.cache, *bt,
                                                 eng.last_token, eng.pos)),
             "prefill": _module(eng._chunk.lower(eng.params, eng.cache, *bt, feed, feed))}
    for kind, name in names.items():
        assert [k for k, fn in run.PROGRAMS.items() if fn in name] == [kind], name


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.docs"])
def test_prefill_useful_share_reads_real_over_computed(tiny_root, cell):
    c = spec.load_cell(tiny_root, cell)
    h = harness.Harness(c, cpu_chip(c, tiny_root)[1], 2**31 + 3, lambda *_: None)
    h.setup(2)
    eng, real, computed = h.engine, [], []
    chunk = eng._chunk

    def counted(*args):  # positions below the pad sentinel are real tokens
        pos = np.asarray(args[-1])
        real.append(int((pos < eng._pad_pos).sum()))
        computed.append(pos.size)
        return chunk(*args)

    eng._chunk = counted
    h.window(2)
    [name] = [m["name"] for m in c.per_layer if m["name"].startswith("prefill_useful_share")]
    value = spec.reader(tiny_root, name)(h.run)
    assert value == pytest.approx(100.0 * sum(real) / sum(computed))
    assert 0 < value < 100


def test_prefill_useful_share_is_silent_without_the_counter():
    old = SimpleNamespace(engine_metrics=SimpleNamespace(prefill_tokens=5))
    for name in ("prefill_useful_share.tail", "prefill_useful_share.tput"):
        assert spec.reader(REPO, name)(old) is None
