"""Bring-up smoke run of the served path on a TPU, through its normal objects.

    python chip_smoke.py              # one chip: dense, paged, logits, kernels
    python chip_smoke.py --four-chips # four chips: 4 replicas and a tp=4 engine

Qwen2.5-14B at every published width, cut to 12 of its 48 layers, with bf16
weights drawn from ``--seed``, is served by ``ServeEngine`` exactly as
``repro.launch.serve`` builds it.  Every phase checks its own results and the
script exits non-zero, printing no result, on the first failed check or when
JAX finds no TPU.  The last stdout line is the JSON result.  Times printed on
the way are single host-clock readings of a bring-up run, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
try:
    from repro.configs import get_config
    from repro.dist.sharding import activation_sharding
    from repro.kernels import api
    from repro.kernels import guard as kguard
    from repro.launch.cache import place_compile_cache
    from repro.models import build_model
    from repro.serve import ClusterConfig, ClusterRouter, EngineConfig, ServeEngine
except ImportError as e:
    raise SystemExit(f"chip_smoke: the repo's src/ is not beside this script: {e}")

ARCH = "qwen2.5-14b"
# every width as published; 12 of 48 layers and bf16 weights so that the
# parameters (9.72 GB) and the 8 x 2048 KV cache fit one 16 GB v5e
N_LAYERS = 12
SLOTS, MAX_LEN, CHUNK, PAGE = 8, 2048, 16, 16
N_REQUESTS, MAX_NEW, PROMPT_LEN = 8, 32, (128, 512)
PREFIX_LEN = 64
# Max-abs bound on last-position logits (std ~1.4 at these random weights)
# between two bf16 paths that differ only in summation order and in where
# bf16 roundings fall: 12 bf16 residual layers drift each by a few bf16 ulps.
# A wrong position, mask or cache write moves logits by about their std.
LOGITS_ATOL = 0.25
HW = "tpu-v5e"  # repro.hw part whose bf16 ulp ladder judges kernel outputs


class SmokeFailure(RuntimeError):
    """A phase's own check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(n_chips: int):
    """The devices, or exit when JAX has no TPU: never run on the CPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform {devices[0].platform!r}); "
            "this run measures the chip and does not fall back to the CPU"
        )
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, found {len(devices)}")
    return devices


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's events."""
    def __init__(self):
    
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def peak_bytes() -> str:
    """Device 0's ``peak_bytes_in_use`` so far (it never decreases)."""
    return str((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", "not reported"))


def make_prompts(vocab: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    return [[int(t) for t in rng.integers(1, vocab, n)] for n in lens]


def engine_config(**kw):
    # degrade=False, guard="off": a failing step raises instead of re-routing
    return EngineConfig(n_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=CHUNK,
                        degrade=False, guard="off", **kw)


def check_served(sessions, summary: dict, vocab: int, what: str) -> int:
    """Every session complete and in-vocabulary; no fallback fired."""
    for s in sessions:
        check(s.done and s.finish_reason == "max_new_tokens" and len(s.out) == MAX_NEW,
              f"{what}: request {s.rid} ended {s.finish_reason!r} with {len(s.out)} tokens")
        check(all(0 <= t < vocab for t in s.out), f"{what}: request {s.rid} left the vocabulary")
    for key in ("degradations", "op_degradations", "quarantines", "nan_events"):
        check(summary[key] == 0, f"{what}: {key} = {summary[key]}")
    return summary["generated_tokens"]


def serve(engine, prompts, what: str, vocab: int) -> list:
    t0 = time.perf_counter()
    sessions = [engine.submit(p, MAX_NEW) for p in prompts]
    engine.run()
    s = engine.summary()
    tokens = check_served(sessions, s, vocab, what)
    print(f"{what}: served {tokens} tokens for {len(sessions)} requests in "
          f"{time.perf_counter() - t0:.2f}s (compiles included); "
          f"cache {nbytes(engine.cache)} bytes; peak so far {peak_bytes()}", flush=True)
    return [list(x.out) for x in sessions]


def prefill_step(model, paged: bool = False, mesh=None):
    """The engine's compiled prefill step (``decode_chunk``), under ``mesh``."""
    fn = model.decode_chunk_paged if paged else model.decode_chunk
    if mesh is None:
        return jax.jit(fn)

    def step(*args):
        with activation_sharding(mesh):
            return fn(*args)

    return jax.jit(step)


def last_logits(step, params, cache, prompt, pad: int, table=None):
    """Feed ``prompt`` on lane 0, ``CHUNK`` tokens per call, with every other
    lane padding; lane 0's float32 logits at the prompt's last position."""
    n = len(prompt)
    width = -(-n // CHUNK) * CHUNK
    toks = np.zeros((SLOTS, width), np.int32)
    poss = np.full((SLOTS, width), pad, np.int32)
    toks[0, :n] = prompt
    poss[0, :n] = np.arange(n)
    extra = () if table is None else (jnp.asarray(table),)
    for c in range(0, width, CHUNK):
        logits, cache = step(params, cache, *extra, jnp.asarray(toks[:, c:c + CHUNK]),
                             jnp.asarray(poss[:, c:c + CHUNK]))
    return np.asarray(logits[0, (n - 1) % CHUNK].astype(jnp.float32))


def check_logits(got, want, what: str) -> float:
    err = float(np.max(np.abs(got - want)))
    print(f"{what}: max |logits diff| {err:.4f} (bound {LOGITS_ATOL}, "
          f"reference max |logit| {float(np.max(np.abs(want))):.3f})", flush=True)
    check(bool(np.all(np.isfinite(got))), f"{what}: non-finite logits")
    check(err <= LOGITS_ATOL, f"{what}: logits differ by {err} > {LOGITS_ATOL}")
    return err


def reference_logits(model, params, prompt):
    """``model.prefill``'s full forward: last-position logits."""
    last, _ = jax.jit(model.prefill)(params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    return np.asarray(last[0].astype(jnp.float32))


def kernel_phase(head_dim: int, n_heads: int, seed: int) -> None:
    """Compiled Pallas kernels against the XLA path on the same inputs."""
    ka, kb, kq, kk, kv = jax.random.split(jax.random.key(seed), 5)
    a = jax.random.normal(ka, (4096, 4096), jnp.bfloat16)
    b = jax.random.normal(kb, (4096, 4096), jnp.bfloat16)
    qkv = [jax.random.normal(k, (1, MAX_LEN, n_heads, head_dim), jnp.bfloat16)
           for k in (kq, kk, kv)]
    cases = {
        "matmul bf16 4096^2": (api.matmul, (a, b), {}),
        f"flash_attention bf16 {n_heads}x{MAX_LEN}x{head_dim} causal":
            (api.flash_attention, qkv, {"causal": True}),
    }
    for name, (op, args, kw) in cases.items():
        got = op(*args, backend="pallas", interpret=False, **kw)
        want = op(*args, backend="xla", **kw)
        rep = kguard.compare(got, want, kguard.tolerance(got.dtype, hw=HW), op=name)
        print(f"kernel {rep.describe()}", flush=True)
        check(rep.ok, f"kernel {name} disagrees with xla")


def init_params(model, seed: int):
    """Random weights from ``seed`` on the default device: one compiled
    program, where eager init compiles a program per weight shape."""
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))
    print(f"{ARCH} {N_LAYERS}/48 layers: {nbytes(params)} parameter bytes, "
          f"initialised in {time.perf_counter() - t0:.1f}s; peak so far {peak_bytes()}",
          flush=True)
    return params


def one_chip(cfg, model, seed: int) -> None:
    params = init_params(model, seed)
    vocab = cfg.vocab_size
    prompts = make_prompts(vocab, seed)
    probe = prompts[0]
    want = reference_logits(model, params, probe)

    engine = model_engine(model, params)
    dense = serve(engine, prompts, "dense", vocab)
    got = last_logits(prefill_step(model), engine.params, engine.cache, probe, MAX_LEN)
    check_logits(got, want, "dense decode_chunk vs prefill")
    del engine
    gc.collect()

    engine = model_engine(model, params, page_size=PAGE)
    rng = np.random.default_rng(seed + 1)
    prefix = [int(t) for t in rng.integers(1, vocab, PREFIX_LEN)]
    engine.register_prefix(prefix)
    shared = [prefix + p if i % 2 == 0 else p for i, p in enumerate(prompts)]
    paged = serve(engine, shared, "paged", vocab)
    hits = engine.summary()["prefix_hits"]
    check(hits == N_REQUESTS // 2, f"paged: {hits} prefix hits, want {N_REQUESTS // 2}")
    same = sum(paged[i] == dense[i] for i in range(1, N_REQUESTS, 2))
    print(f"paged: {hits} prefix hits; unshared requests token-equal to dense: "
          f"{same}/{N_REQUESTS // 2} (information, not a check)", flush=True)
    pages = engine.allocator.alloc(engine.allocator.pages_for(len(probe)))
    table = np.zeros((SLOTS, engine.cfg.table_width), np.int32)
    table[0, :len(pages)] = pages
    got = last_logits(prefill_step(model, paged=True), engine.params, engine.cache, probe,
                      engine.cfg.table_width * PAGE, table)
    engine.allocator.free(pages)
    check_logits(got, want, "paged decode_chunk_paged vs prefill")
    del engine
    gc.collect()
    kernel_phase(cfg.head_dim, cfg.n_heads, seed)


def model_engine(model, params, **kw):
    return ServeEngine(model, params, engine_config(**kw))


def four_chips(cfg, model, devices, seed: int) -> None:
    """Four one-chip replicas behind ``ClusterRouter`` against one one-chip
    engine (token-exact), and a tp=4 engine's prefill logits against it."""
    # every engine places its own copy from the host: device 0 holds one
    host = jax.device_get(init_params(model, seed))
    vocab = cfg.vocab_size
    prompts = make_prompts(vocab, seed)
    probe = prompts[0]
    # the replicas' own program: a one-device "model" mesh on device 0
    mesh1 = Mesh(np.array(devices[:1]), ("model",))
    engine = model_engine(model, host, mesh=mesh1)
    want_tokens = serve(engine, prompts, "one-chip reference", vocab)
    want = last_logits(prefill_step(model, mesh=mesh1), engine.params, engine.cache,
                       probe, MAX_LEN)
    del engine
    gc.collect()

    cluster = ClusterRouter(model, host, ClusterConfig(
        engine=engine_config(), n_replicas=4, router="round_robin",
        devices=tuple(devices[:4])))
    sessions = [cluster.submit(p, MAX_NEW) for p in prompts]
    cluster.run()
    check_served(sessions, cluster.summary(), vocab, "4 replicas")
    homes = []
    for r in cluster.replicas:
        placed = {d for leaf in jax.tree.leaves(r.engine.params) for d in leaf.devices()}
        check(placed == {r.mesh.devices.flat[0]},
              f"replica {r.index} parameters on {sorted(map(str, placed))}")
        homes.append(r.mesh.devices.flat[0])
    check(len(set(homes)) == 4, f"replicas share devices: {homes}")
    same = [list(s.out) == w for s, w in zip(sessions, want_tokens)]
    print(f"4 replicas on {[str(d) for d in homes]}: tokens equal to the one-chip "
          f"engine for {sum(same)}/{len(same)} requests", flush=True)
    check(all(same), "replica tokens differ from the one-chip engine's")
    del cluster, sessions
    gc.collect()

    mesh4 = Mesh(np.array(devices[:4]), ("model",))
    engine = model_engine(model, host, mesh=mesh4)
    got = last_logits(prefill_step(model, mesh=mesh4), engine.params, engine.cache,
                      probe, MAX_LEN)
    check_logits(got, want, "tp=4 prefill vs one-chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase (4 replicas, tp=4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    cache_dir = place_compile_cache()
    devices = require_tpu(n_chips)
    clock = CompileClock()
    dev = devices[0]
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache: "
          f"{cache_dir or 'JAX_COMPILATION_CACHE_DIR'}", flush=True)

    cfg = get_config(ARCH).replace(n_layers=N_LAYERS, param_dtype="bfloat16")
    model = build_model(cfg)
    try:
        if args.four_chips:
            four_chips(cfg, model, devices, args.seed)
        else:
            one_chip(cfg, model, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {peak_bytes()} of "
          f"bytes_limit {stats.get('bytes_limit', 'not reported')} on {dev}; "
          f"backend compile {clock.seconds:.1f}s, {clock.cache_hits} persistent-cache hits",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
