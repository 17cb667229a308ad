"""Bitmap-index queries through ``PudEngine.run_program``, closed loop.

The configuration's daily bitmaps and the gender bitmap are made on the
device from the seed in one jitted call, one packed ``(rows, words)``
uint32 plane each.  One client sends queries back to back: each query
takes the ``weeks`` weeks that end on a day drawn from the seed, runs the
compiled Program (a weekly OR over 7 daily planes, the AND over the weeks,
and the AND with the gender plane) on the engine, then counts both result
planes on the device and brings the two counts to the host.  A query's
latency runs from its send to both counts on the host; ``query_p95_ms`` is
the 95th percentile over every query of the window.

For a sample of the window's queries, drawn from the seed, the result
planes and counts are kept; after the window they are compared bit for
bit with the plain reference on the same input planes
(``wrong_answers``: the queries whose planes or counts differ).
"""
from __future__ import annotations

import time

import numpy as np

import harness
import kernel_bytes


def _fmix32(x):
    """MurmurHash3's 32-bit finalizer: a bijective bit mixer."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def make_planes(key, n_planes: int, rows: int, words: int):
    """``n_planes`` uniform random ``(rows, words)`` uint32 planes from a
    32-bit key, in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(k):
        idx = jnp.arange(rows * words, dtype=jnp.uint32).reshape(rows, words)
        k = _fmix32(k ^ jnp.uint32(0x9E3779B9))
        return tuple(_fmix32(_fmix32(idx + jnp.uint32(d * rows * words)) ^ k)
                     for d in range(n_planes))

    return list(gen(jnp.uint32(key)))


def query_program(weeks: int):
    from repro.core import compiler as CC
    v = CC.Var
    week = [CC.Or([v(f"d{7 * w + i}") for i in range(7)])
            for w in range(weeks)]
    return CC.compile_expr({"active": CC.And(week),
                            "male": CC.And([v("g"), *week])})


class Driver:
    def __init__(self, run: harness.Run):
        self.run = run
        cfg, mix = run.config, run.traffic
        self.rows, self.words = int(cfg["plane_rows"]), int(cfg["plane_words"])
        if self.rows * self.words * 32 != int(cfg["users"]):
            raise ValueError("plane_rows x plane_words x 32 must be users")
        self.days = int(cfg["days"])
        self.weeks = int(mix["weeks"])
        self.query_rng = np.random.default_rng([run.seed, 3])
        self.sample_rng = np.random.default_rng([run.seed, 4])
        self.sample_size = int(mix["sample"])
        self.seen = 0
        self.kept: list = []
        self.latency_s: list[float] = []

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.kernels import ops as kops
        from repro.pud.engine import PudEngine
        self.run.inst.wrap(kops, "nary_bitwise", kernel="nary_bitwise",
                           nbytes=lambda planes, *a, **k:
                               kernel_bytes.nary_bitwise(planes.shape))
        key = int(np.random.SeedSequence(self.run.seed)
                  .generate_state(1)[0])
        # days 0 .. days-1, then the gender plane
        self.planes = make_planes(key, self.days + 1, self.rows, self.words)
        self.prog = query_program(self.weeks)
        self.engine = PudEngine("pallas")

        def popcount(p):
            return jnp.sum(jax.lax.population_count(p), dtype=jnp.int32)

        if self.run.variant == "control":
            # the guarantee it breaks: exact counts (every other row, x2)
            def count(a, m):
                return 2 * popcount(a[::2]), 2 * popcount(m[::2])
        else:
            def count(a, m):
                return popcount(a), popcount(m)
        self.count = jax.jit(count)
        warm = np.random.default_rng(0)
        for _ in range(2):
            self._query(int(warm.integers(7 * self.weeks - 1, self.days)))

    def _query(self, end: int):
        days = self.run.reference.week_days(end, self.weeks)
        ins = {f"d{j}": self.planes[d] for j, d in enumerate(days)}
        ins["g"] = self.planes[self.days]
        with self.run.inst.span("query"):
            with self.run.inst.span("program"):
                out = self.engine.run_program(self.prog, ins)
            a, m = self.count(out["active"], out["male"])
            return out, int(a), int(m)

    def unit(self, i: int) -> int:
        end = int(self.query_rng.integers(7 * self.weeks - 1, self.days))
        t0 = time.perf_counter()
        out, a, m = self._query(end)
        self.latency_s.append(time.perf_counter() - t0)
        self.seen += 1
        if len(self.kept) < self.sample_size:
            self.kept.append((end, out, a, m))
        else:
            j = int(self.sample_rng.integers(self.seen))
            if j < self.sample_size:
                self.kept[j] = (end, out, a, m)
        return 1

    def end_to_end(self, window_s: float, units: int, work: float) -> dict:
        return {"queries_per_s": units / window_s,
                "query_p95_ms": 1e3 * float(np.percentile(self.latency_s,
                                                          95))}

    def release(self) -> None:
        """Bring the kept answers and their input planes to the host, then
        drop every device array."""
        host = {}
        kept = []
        for end, out, a, m in self.kept:
            for d in [*self.run.reference.week_days(end, self.weeks),
                      self.days]:
                if d not in host:
                    host[d] = np.asarray(self.planes[d])
            kept.append((end, np.asarray(out["active"]),
                         np.asarray(out["male"]), a, m))
        self.kept, self.host_planes = kept, host
        self.planes = self.engine = self.count = None

    def check(self) -> list[harness.Check]:
        ref = self.run.reference
        wrong = 0
        for end, active, male, a, m in self.kept:
            days = [self.host_planes[d]
                    for d in ref.week_days(end, self.weeks)]
            w_active, w_male, w_a, w_m = ref.query(
                days, self.host_planes[self.days])
            wrong += not (np.array_equal(active, w_active)
                          and np.array_equal(male, w_male)
                          and a == w_a and m == w_m)
        if not self.kept:
            wrong = np.inf
        return [harness.Check("wrong_answers", float(wrong), 0.0)]
