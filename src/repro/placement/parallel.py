"""Process-parallel exact-DES plan evaluation.

The exact tier of every search (``screened_search`` / ``robust_search``
/ ``region_search``) scores a shortlist of finalist plans with the full
DES replay — each an independent, CPU-bound ``engine.run_plan`` call on
one shared, already-driven fire trace. :class:`ParallelEvaluator` fans
those calls across a persistent worker pool:

* **workers > 1**: workers are *spawned* and rebuild the engine from the
  scenario's JSON ``ScenarioSpec`` (``spec=``), paying one functional
  drive each, once per pool lifetime. Spawn, not fork: the parent may
  hold the TPU (the fluid ensemble runs there), and a forked child
  would inherit its multithreaded runtime. Workers never initialise a
  JAX backend — the DES is host code — and every result carries a
  check of that, so a worker that touched JAX fails the batch.
* **workers <= 1**: no pool — the batch runs the base class's serial
  loop in the caller's process.

A pool that cannot start or dies raises; nothing falls back silently.

Determinism: ``run_plan`` is a pure function of (driven engine, plan),
so per-plan results do not depend on which worker computes them. The
merge replays the submission order exactly as the serial evaluator
would — cache inserts, history entries and hit/miss counters are
bit-identical for any worker count, including the in-process fallback.

The memo cache is the inherited :class:`~repro.placement.search.
Evaluator` cache, shared across calls (and across searches when the
caller passes ``cache=``), so an online controller's epoch loop reuses
exact results instead of re-fanning them out.
"""
from __future__ import annotations

import multiprocessing as mp
import os
from typing import Dict, List, Optional, Sequence, Tuple

from jax._src import xla_bridge

from repro.placement.cosim import CoSimResult, CoSimulator
from repro.placement.plan import PlacementPlan
from repro.placement.search import Evaluator

# Worker-process state: the engine every task of this pool evaluates
# against. Set once by the pool initializer.
_WORKER_ENGINE = None


def _init_worker(spec_dict: Dict) -> None:
    global _WORKER_ENGINE
    from repro.scenario.spec import ScenarioSpec
    engine = ScenarioSpec.from_dict(spec_dict).compile()
    engine._ensure_driven()
    _WORKER_ENGINE = engine


def _eval_plan(plan_dict: Dict) -> Tuple[CoSimResult, bool]:
    """One exact-DES replay, plus whether this process has initialised
    a JAX backend (jax exposes that check only privately)."""
    res = _WORKER_ENGINE.run_plan(PlacementPlan.from_dict(plan_dict))
    return res, xla_bridge.backends_are_initialized()


def default_workers() -> int:
    """Pool width when the caller does not pin one: the machine's cores
    (a 1-core box degrades to the in-process serial path)."""
    return os.cpu_count() or 1


class ParallelEvaluator(Evaluator):
    """Drop-in :class:`Evaluator` whose :meth:`evaluate_batch` fans the
    *uncached* plans of a batch across a persistent process pool.

    Single-plan ``__call__`` stays in-process (one DES run gains
    nothing from a pool round-trip); searches batch their exact tiers,
    so the pool sees the finalist fan-outs. Close with :meth:`close`
    or use as a context manager; an unclosed pool is reaped with the
    evaluator.

    Parameters
    ----------
    cosim:
        The driven scorer (a ``ScenarioEngine``).
    workers:
        Pool width; ``None`` means :func:`default_workers`. ``<= 1``
        disables the pool entirely (serial in-process evaluation).
    spec:
        The ``ScenarioSpec`` (or its ``to_dict()`` form) ``cosim`` was
        compiled from; the workers rebuild their engines from it.
        Required when ``workers > 1``.
    """

    def __init__(self, cosim: CoSimulator, workers: Optional[int] = None,
                 spec=None, screener=None,
                 cache: Optional[Dict[Tuple, CoSimResult]] = None,
                 key_prefix: Optional[Tuple] = None):
        super().__init__(cosim, screener=screener, cache=cache,
                         key_prefix=key_prefix)
        self.workers = default_workers() if workers is None else int(workers)
        self._spec_dict = (spec.to_dict() if hasattr(spec, "to_dict")
                          else spec)
        if self.workers > 1 and self._spec_dict is None:
            raise ValueError("a worker pool rebuilds the engine from its "
                             "ScenarioSpec: pass spec= (or workers=1)")
        self._pool = None
        self.parallel_batches = 0   # batches that actually used the pool
        self.parallel_jobs = 0      # plans evaluated by pool workers
        self.serial_jobs = 0        # uncached plans evaluated in-process

    # ------------------------------------------------------------- pool
    def _ensure_pool(self):
        if self.workers <= 1:
            return None
        if self._pool is None:
            self._pool = mp.get_context("spawn").Pool(
                processes=self.workers, initializer=_init_worker,
                initargs=(self._spec_dict,))
        return self._pool

    def close(self) -> None:
        """Shut the worker pool down (idempotent)."""
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def __enter__(self) -> "ParallelEvaluator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # ------------------------------------------------------------ batch
    def evaluate_batch(self, plans: Sequence[PlacementPlan]
                       ) -> List[CoSimResult]:
        """Fan the batch's uncached unique plans across the pool, then
        replay the submission order against the cache — the resulting
        cache contents, history order, and hit/miss counters are
        bit-identical to the serial base class for any worker count."""
        todo: List[PlacementPlan] = []
        seen = set()
        for plan in plans:
            key = self._key(plan)
            if key not in self.cache and key not in seen:
                seen.add(key)
                todo.append(plan)
        pool = self._ensure_pool() if len(todo) > 1 else None
        fresh: Dict[Tuple, CoSimResult] = {}
        if pool is not None:
            results = pool.map(_eval_plan, [p.to_dict() for p in todo])
            if any(touched for _, touched in results):
                raise RuntimeError("a pool worker initialised a JAX "
                                   "backend; workers must stay off the "
                                   "device the parent may hold")
            self.parallel_batches += 1
            self.parallel_jobs += len(todo)
            fresh = {self._key(p): r for p, (r, _) in zip(todo, results)}
        out: List[CoSimResult] = []
        for plan in plans:
            key = self._key(plan)
            if key in self.cache:
                self.hits += 1
            else:
                self.misses += 1
                res = fresh.get(key)
                if res is None:
                    res = self._run(plan)
                    self.serial_jobs += 1
                self.cache[key] = res
                self.history.append((plan.label, res.vos))
            out.append(self.cache[key])
        return out

    def stats(self) -> Dict:
        out = super().stats()
        out.update({"workers": self.workers,
                    "parallel_batches": self.parallel_batches,
                    "parallel_jobs": self.parallel_jobs,
                    "serial_jobs": self.serial_jobs})
        return out
