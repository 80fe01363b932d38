"""Extraction of per-device FLOPs/bytes from compiled executables."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any


@dataclass
class CompiledCosts:
    flops_per_device: float
    bytes_per_device: float
    transcendentals: float
    # memory analysis (per device)
    arg_bytes: int
    out_bytes: int
    temp_bytes: int
    alias_bytes: int
    code_bytes: int

    @property
    def peak_hbm_bytes(self) -> int:
        """Live-at-once estimate: args + outputs + temps - aliased.

        NOTE: on the CPU dry-run backend this OVERESTIMATES bf16-heavy
        footprints — XLA:CPU legalizes bf16 buffers by keeping f32 copies
        (observed as convert()'d duplicate stacks in the HLO).  The analytic
        estimate in the dry-run record is the TPU-expectation counterpart.
        """
        return self.arg_bytes + self.out_bytes + self.temp_bytes - self.alias_bytes

    def summary(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "transcendentals": self.transcendentals,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
        }


def extract_costs(compiled: Any) -> CompiledCosts:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    return CompiledCosts(
        flops_per_device=float(ca.get("flops", 0.0)),
        bytes_per_device=float(ca.get("bytes accessed", 0.0)),
        transcendentals=float(ca.get("transcendentals", 0.0)),
        arg_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        out_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        alias_bytes=int(getattr(ma, "alias_size_in_bytes", 0)),
        code_bytes=int(getattr(ma, "generated_code_size_in_bytes", 0)),
    )
