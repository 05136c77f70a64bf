"""Calibrate ``flops_per_record`` from Pallas kernel dry-runs.

Scenario profiles used to *declare* per-service operator cost; this
module *measures* it: the service's operator kernel (``window_agg``,
``ssd_scan`` or ``flash_attention``) is compiled on a canonical shape
derived from the service's window — for the TPU, or in the Pallas
interpreter where the caller asks for it — and XLA's compiled cost
analysis reports the FLOP count, normalized per ingested record.
That number feeds the same roofline cost cells
(:func:`repro.scenario.engine.analytics_cost_model`) the DC simulator
prices VDC steps with — closing the ROADMAP item "learn per-service
flops_per_record from measured kernel dry-runs".

When XLA cannot cost the program (backend without cost analysis), a
documented analytic fallback keeps calibration deterministic and
dependency-free. A kernel that fails to lower or compile raises.

Usage::

    cal = KernelCalibrator()                   # interpret=True on a CPU
    engine = spec.compile(calibrator=cal)      # measured profiles
    print(cal.report())                        # what was measured

``benchmarks/run.py --calibrate`` threads a calibrator through the
placement benchmark.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

_INTENSITY = {          # analytic flops/record fallbacks, by operator
    # one VPU op per element in the segment phase + m-way combine
    "window_agg": lambda m: 1.0 + 1.0 / 64.0 * m,
    # per timestep: state update (2·N·P) + readout (2·N·P) + decay
    "ssd_scan": lambda m: 4.0 * 16 * 64 + 16,
    # per query row: QK^T + PV at S=256, d=64 → 4·S·d
    "flash_attention": lambda m: 4.0 * 256 * 64,
}


@dataclasses.dataclass(frozen=True)
class Calibration:
    """One measured operator cost."""
    operator: str
    agg: str
    m: int                      # window/stride ratio the shape encoded
    n_records: int              # records the dry-run ingested
    flops_total: float
    flops_per_record: float
    source: str                 # "xla-cost-analysis" | "analytic"


def _cost_flops(jitted, *args) -> Optional[float]:
    """FLOPs of a compiled program via XLA cost analysis (None when the
    backend does not expose one). Tracing, lowering and compile errors
    propagate — a kernel the compiler refuses for the requested shape
    is a real calibration bug, not a missing-cost-analysis backend."""
    ca = jitted.lower(*args).compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return None
    flops = ca.get("flops")
    return float(flops) if flops and flops > 0 else None


class KernelCalibrator:
    """Measures (and caches) flops_per_record per operator family.

    Callable with a :class:`~repro.scenario.spec.ServiceSpec` so it can
    be passed straight to ``ScenarioSpec.compile(calibrator=...)``.
    The kernels compile for the TPU unless the caller passes
    ``interpret=True`` (the Pallas interpreter, which a CPU needs) —
    fine for cost analysis, which reads the compiled program, not the
    wall clock."""

    def __init__(self, interpret: bool = False, stride: int = 64):
        self.interpret = interpret
        self.stride = stride
        self._cache: Dict[Tuple[str, str, int], Calibration] = {}
        self.log: List[Calibration] = []

    # ------------------------------------------------------------ frontends
    def __call__(self, svc) -> float:
        m = max(1, min(8, round(svc.width_s / max(svc.slide_s, 1e-9))))
        return self.measure(svc.operator, agg=svc.agg, m=m).flops_per_record

    def measure(self, operator: str, agg: str = "max",
                m: int = 2) -> Calibration:
        agg = {"count": "sum"}.get(agg, agg)
        if operator not in _INTENSITY:
            raise ValueError(f"unknown operator {operator!r} "
                             f"(known: {sorted(_INTENSITY)})")
        key = (operator, agg if operator == "window_agg" else "-", m)
        if key not in self._cache:
            cal = self._measure(operator, agg, m)
            self._cache[key] = cal
            self.log.append(cal)
        return self._cache[key]

    def report(self) -> List[Dict]:
        return [dataclasses.asdict(c) for c in self.log]

    # ------------------------------------------------------------ dry-runs
    def _measure(self, operator: str, agg: str, m: int) -> Calibration:
        fn = getattr(self, f"_dry_{operator}")
        flops, n_records = fn(agg, m)
        if flops is None:
            fpr = _INTENSITY[operator](m)
            return Calibration(operator, agg, m, n_records,
                               flops_total=fpr * n_records,
                               flops_per_record=fpr, source="analytic")
        return Calibration(operator, agg, m, n_records, flops_total=flops,
                           flops_per_record=flops / n_records,
                           source="xla-cost-analysis")

    def _dry_window_agg(self, agg: str, m: int):
        import jax
        import jax.numpy as jnp
        from repro.kernels.window_agg.ops import window_aggregate

        stride = self.stride
        window = m * stride
        T = 4 * window
        x = jnp.ones((T, 1), jnp.float32)
        f = jax.jit(lambda a: window_aggregate(
            a, agg=agg, window=window, stride=stride,
            interpret=self.interpret))
        return _cost_flops(f, x), T

    def _dry_ssd_scan(self, agg: str, m: int):
        import jax
        import jax.numpy as jnp
        from repro.kernels.ssd_scan.ops import ssd_scan

        B, L, H, P, G, N = 1, 128, 2, 64, 1, 16
        x = jnp.ones((B, L, H, P), jnp.float32)
        dt = jnp.ones((B, L, H), jnp.float32) * 0.1
        A = -jnp.ones((H,), jnp.float32)
        Bq = jnp.ones((B, L, G, N), jnp.float32)
        Cq = jnp.ones((B, L, G, N), jnp.float32)
        f = jax.jit(lambda *a: ssd_scan(*a, chunk=64,
                                        interpret=self.interpret))
        return _cost_flops(f, x, dt, A, Bq, Cq), B * L

    def _dry_flash_attention(self, agg: str, m: int):
        import jax
        import jax.numpy as jnp
        from repro.kernels.flash_attention.ops import flash_attention

        B, S, H, d = 1, 256, 2, 64
        q = jnp.ones((B, S, H, d), jnp.float32)
        k = jnp.ones((B, S, H, d), jnp.float32)
        f = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, interpret=self.interpret))
        return _cost_flops(f, q, k, k), B * S


def calibrate_profiles(spec, calibrator: Optional[KernelCalibrator] = None):
    """Measured :class:`ServiceProfile`s for every service of ``spec``
    (declared flops are ignored; SLO/bytes kept). Returns
    ``(profiles, calibrator)`` so callers can read the report."""
    from repro.scenario.profiles import ServiceProfile

    cal = calibrator or KernelCalibrator()
    profiles = {
        s.name: ServiceProfile(slo=s.slo, flops_per_record=cal(s),
                               bytes_per_record=s.bytes_per_record,
                               operator=s.operator)
        for s in spec.services}
    return profiles, cal
