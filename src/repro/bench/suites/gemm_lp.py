"""Tab 3.1 / 4.3 low-precision GEMM suite — the paper's TensorCore story.

The headline of the T4 dissection is the per-dtype throughput ladder:
fp16 TensorCore matmul runs ~5.8x fp32, int8 ~1.8x fp16 (Table 4.3).  This
suite reproduces that contrast as *measured* schema-v1 records — a dtype x
size sweep through the kernel-dispatch API where each dot accumulates via
``preferred_element_type`` (int8 -> int32, floats -> fp32) — plus the
*modeled* ladder for a reference part from the :mod:`repro.hw` spec
database, so a results file carries both the measurement and the
paper-anchored ratios it is validated against.

Registered per backend (``gemm_lp[pallas]`` / ``gemm_lp[xla]``): the Pallas
kernel path and the XLA library path measure the same sweep side by side.
A dtype listed in ``_UNSUPPORTED`` for the backend and platform is skipped
with a note before anything runs; any other failure raises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import repro.hw as hw_db
from repro.core.registry import register
from repro.core.timing import time_fn
from repro.kernels import api

from ..schema import BenchRecord

_JNP_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int8": jnp.int8,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}
_ACC_DTYPES = {"int8": jnp.int32}  # everything else accumulates in fp32

# (dtype, backend, platform) whose matmul cannot run: Mosaic on TPU v5e has
# no float16 vector load
_UNSUPPORTED = {("float16", "pallas", "tpu")}

# ratio records anchor each precision against fp32 (the paper's Tab 4.3
# presentation: "fp16 runs 5.8x fp32, int8 10.4x"), plus the int8-vs-fp16
# TensorCore step the T4 story highlights
_RATIO_ANCHOR = "float32"
_EXTRA_RATIOS = (("int8", "float16"),)


def _measure_one(n: int, dtype: str, backend: str) -> float:
    """GFLOP/s of an n^3 matmul in ``dtype`` on ``backend``."""
    jdt = _JNP_DTYPES[dtype]
    acc = _ACC_DTYPES.get(dtype, jnp.float32)
    a = jnp.ones((n, n), jdt)
    b = jnp.ones((n, n), jdt)
    if backend == "xla":
        fn = jax.jit(
            lambda a, b: jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())), preferred_element_type=acc
            )
        )
    else:
        fn = api.matmul.bound(a, b, out_dtype=acc, backend=backend)
    t = time_fn(fn, a, b, warmup=2, reps=5)
    return 2 * n**3 / t.min_s / 1e9


@register(
    "gemm_lp",
    backends=("pallas", "xla"),
    paper_ref="Tab 3.1 / Tab 4.3 (TensorCore dtypes)",
    description="low-precision matmul throughput: dtype x size sweep + modeled ladder",
    quick={"sizes": (128, 256), "dtypes": ("float32", "bfloat16", "int8")},
    full={
        "sizes": (256, 512, 1024),
        "dtypes": ("float32", "bfloat16", "float16", "int8", "float8_e4m3fn"),
    },
)
def bench_gemm_lp(
    sizes=(128, 256),
    dtypes=("float32", "bfloat16", "int8"),
    hw="T4",
    backend="xla",
) -> list:
    part = hw_db.resolve(hw)
    platform = jax.default_backend()
    recs, skipped, measured = [], [], {}
    for dt in dtypes:
        for n in sizes:
            if (dt, backend, platform) in _UNSUPPORTED:
                skipped.append(f"{dt}:{n}")
                continue
            g = _measure_one(n, dt, backend)
            measured[(dt, n)] = g
            recs.append(
                BenchRecord(
                    name=f"gemm_lp_{dt}:{n}",
                    benchmark="gemm_lp",
                    x=f"{dt}:{n}",
                    value=g,
                    unit="GFLOP/s",
                    metrics={"us_per_call": 2 * n**3 / (g * 1e9) * 1e6},
                    info=f"{backend} backend, preferred_element_type accumulate",
                )
            )
    # measured dtype ratios at the largest size — the host's own ladder
    # (info rows: host CPUs have no TensorCores, so these won't match the
    # GPU ladder; the point is that the *record shape* matches the model's)
    top = max(sizes)
    for dt in dtypes:
        if dt != _RATIO_ANCHOR and (dt, top) in measured and (_RATIO_ANCHOR, top) in measured:
            recs.append(
                BenchRecord(
                    name=f"gemm_lp_measured_ratio_{dt}_over_{_RATIO_ANCHOR}",
                    benchmark="gemm_lp",
                    x=f"{dt}/{_RATIO_ANCHOR}",
                    value=measured[(dt, top)] / measured[(_RATIO_ANCHOR, top)],
                    unit="x",
                    better="info",
                    info=f"measured host ladder at n={top}",
                )
            )
    # the modeled ladder from the spec DB: per-dtype peaks for the reference
    # part and the paper-anchored ratios the validation test asserts on
    for dt in part.dtypes():
        recs.append(
            BenchRecord(
                name=f"gemm_lp_model_{part.name}_{dt}",
                benchmark="gemm_lp",
                x=dt,
                value=part.peak(dt) / 1e12,
                unit="TFLOP/s",
                measured=False,
                info=f"spec-DB peak ({part.source})",
            )
        )
    ratio_pairs = [
        (dt, _RATIO_ANCHOR) for dt in part.dtypes() if dt != _RATIO_ANCHOR
    ] + list(_EXTRA_RATIOS)
    for hi, lo in ratio_pairs:
        if part.supports(lo) and part.supports(hi):
            recs.append(
                BenchRecord(
                    name=f"gemm_lp_model_{part.name}_ratio_{hi}_over_{lo}",
                    benchmark="gemm_lp",
                    x=f"{hi}/{lo}",
                    value=part.peak(hi) / part.peak(lo),
                    unit="x",
                    better="info",
                    measured=False,
                    info="modeled dtype ladder (paper Tab 4.3 for T4)",
                )
            )
    if skipped:
        recs.append(
            BenchRecord(
                name="gemm_lp_skipped",
                benchmark="gemm_lp",
                x=None,
                value=float(len(skipped)),
                unit="points",
                better="info",
                info="unsupported on this backend/platform: " + ", ".join(skipped),
            )
        )
    return recs
