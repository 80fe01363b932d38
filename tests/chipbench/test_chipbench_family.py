"""A configuration names its family, and the family is a file: a new one is
added by files alone, a missing one is refused with the file to add, and no
shared module knows the dense family's leaves or fields."""
import json
import re

import jax
import numpy as np
import pytest
from conftest import REPO, build_root, cpu_chip

from chipbench import run, spec, weights

# the dense family's parameter leaves, ModelConfig fields and config keys
DENSE_NAMES = re.compile(
    r"\b(wq|wk|wv|wo|bq|bk|bv|wi_gate|wi_up|attn_norm|mlp_norm|n_kv_heads|n_heads|d_ff|"
    r"d_model|head_dim|qkv_bias|rope_theta|hidden_size|intermediate_size|num_attention_heads|"
    r"num_key_value_heads|attention_bias)\b")


def _add_cell(root, family: str) -> str:
    """A configuration ``tiny-<family>`` and its chat cell, as new files and
    entries beside the tiny cells."""
    cb = root / "chipbench"
    conf = dict(json.loads((cb / "configs" / "tiny.json").read_text()), family=family)
    name = f"tiny-{family}"
    (cb / "configs" / f"{name}.json").write_text(json.dumps(dict(conf, name=name)))
    limits = cb / "limits"
    (limits / f"{name}.chat.json").write_text((limits / "tiny.chat.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test", "reduced": [], "why": "t",
                             "file": f"chipbench/configs/{name}.json"})
    bench["workloads"].append({"name": f"{name}.chat", "config": name, "traffic": "chat",
                               "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.chat" in m.get("workloads", ()):
            m["workloads"].append(f"{name}.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return f"{name}.chat"


def test_a_family_is_added_by_files_alone(tmp_path):
    """``dense_wide_norm`` (``data/families``): the dense layer with a weight
    rule of its own, through set-up, the window and ``check.compare``."""
    root = build_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*.py")}
    cell = spec.load_cell(root, _add_cell(root, "dense_wide_norm"))
    dense = spec.family(root, {"family": "dense_gqa"}, "test")
    assert cell.family is not dense and cell.family.layer is dense.layer
    args = run.parse(["--workload", cell.name, "--seed", str(2**33 + 9), "--seconds", "2"])
    res = run.measure(args, root=root, chip_fn=cpu_chip)
    assert res["correct"], res["checks"]
    assert res["checks"]["sampled_tokens"]["value"] >= 16
    # its own rule took effect, and only on the norm scales
    from repro.models import build_model

    shapes = jax.eval_shape(build_model(dense.program_config(cell.config)).init,
                            jax.random.key(0))
    flat = [jax.tree_util.tree_flatten_with_path(weights.make(shapes, 5, f.leaf_std))[0]
            for f in (dense, cell.family)]
    for (path, a), (_, b) in zip(*flat):
        same = np.array_equal(np.asarray(a), np.asarray(b))
        assert same == (path[-1].key != "scale"), path
    assert all(p.read_bytes() == b for p, b in before.items())  # nothing edited


@pytest.mark.parametrize("family,error,names", [
    (None, KeyError, r"names no family.*chipbench/families/<name>\.py"),
    ("no_such", FileNotFoundError, r"chipbench/families/no_such\.py"),
])
def test_a_configuration_without_its_family_file_is_refused(tmp_path, family, error, names):
    """Never a fallback to the dense family: the error names the file to add."""
    root = build_root(tmp_path)
    path = root / "chipbench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    del conf["family"]
    if family:
        conf["family"] = family
    path.write_text(json.dumps(conf))
    with pytest.raises(error, match=names):
        spec.load_cell(root, "tiny.chat")


def test_no_shared_module_names_a_dense_leaf_or_field():
    found = [f"{p.relative_to(REPO)}:{i}: {line.strip()}"
             for p in sorted((REPO / "chipbench").rglob("*.py"))
             if "families" not in p.parts
             for i, line in enumerate(p.read_text().splitlines(), 1)
             if DENSE_NAMES.search(line)]
    assert found == []
