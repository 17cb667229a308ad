"""JAX twin of the closed-form reliability model + one-shot MC sampling.

Two layers:

* **Closed form** — jitted re-implementations of ``analog.mixture_cdf`` /
  ``boolean_success`` / ``not_success``.  The op-context scalars (sigma,
  spike weights, floor, shifts) are cheap Python math and are computed by
  ``repro.core.analog``; only the array math runs under ``jax.jit``.
* **Sampling** — ``sample_boolean_success`` / ``sample_not_success`` draw a
  full ``(trials, width)`` Monte-Carlo estimate of the cell-averaged model
  in one jitted call: random operands, per-column popcount, success-table
  lookup, Bernoulli outcome.  This is the paper's 10,000-trial protocol at
  closed-form fidelity, and runs ~3 orders of magnitude faster than the
  command-level ``BankSim`` loop — use it for quick sweeps; use the batched
  ``BankSim(trials=T)`` when command-level effects (pair selection, Frac
  staging, reference-side readout) matter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import analog as A
from .analog import DEFAULT_PARAMS


# ---------------------------------------------------------------------------
# Closed form, jitted
# ---------------------------------------------------------------------------
def phi(z):
    """Standard normal CDF (jax)."""
    return 0.5 * (1.0 + jax.scipy.special.erf(z / jnp.sqrt(2.0)))


def mixture_cdf(x, s, b, w_plus, w_minus):
    """jax twin of :func:`repro.core.analog.mixture_cdf`."""
    return ((1.0 - w_plus - w_minus) * phi(x / s)
            + w_plus * phi((x + b) / s)
            + w_minus * phi((x - b) / s))


@jax.jit
def _success_table_kernel(m, dv, shift, s, b, wp, wm, pf, ideal):
    x = m + dv - shift
    p1 = mixture_cdf(x, s, b, wp, wm)
    s_analog = jnp.where(ideal, p1, 1.0 - p1)
    return (1.0 - pf) * s_analog + 0.5 * pf


def _context(op: str, n: int, *, p=DEFAULT_PARAMS, temp_c=50.0,
             random_pattern=True, speed_mts=2666, compute_region=A.MIDDLE,
             ref_region=A.MIDDLE, mfr="sk_hynix", density_gb=4, die_rev="A"):
    """Scalar op context (pure Python, identical to the numpy oracle)."""
    s, b, wp, wm = A.op_noise(op, n, p, temp_c=temp_c,
                              random_pattern=random_pattern,
                              speed_mts=speed_mts, mfr=mfr,
                              density_gb=density_gb, die_rev=die_rev)
    dv = A.margin_offset(op, p, compute_region=compute_region,
                         ref_region=ref_region, mfr=mfr,
                         density_gb=density_gb, die_rev=die_rev)
    shift = A.op_shift(op, n, p) + p.delta_v
    pf = A.op_pfloor(op, n, p, temp_c=temp_c, random_pattern=random_pattern,
                     speed_mts=speed_mts)
    return s, b, wp, wm, dv, shift, pf


def boolean_success_table(op: str, n: int, **kw):
    """(n+1,) P(correct) per #logic-1 operands — jitted array math."""
    p = kw.get("p", DEFAULT_PARAMS)
    s, b, wp, wm, dv, shift, pf = _context(op, n, **kw)
    k = np.arange(n + 1)
    m = A.op_margin(op, n, k, p)
    ideal = A.op_ideal("and" if A._base_op(op)[0] == "and" else "or", n, k)
    return _success_table_kernel(jnp.asarray(m), dv, shift, s, b, wp, wm, pf,
                                 jnp.asarray(ideal))


def boolean_success_avg(op: str, n: int, **kw) -> float:
    """jax twin of :func:`repro.core.analog.boolean_success_avg`."""
    table = boolean_success_table(op, n, **kw)
    return float(jnp.sum(jnp.asarray(A.binomial_weights(n)) * table))


def not_success(n_dst: int, **kw) -> float:
    """NOT success; scalar closed form — delegates to the numpy oracle (the
    jax win is in the samplers below, not in 3-term scalar math)."""
    return A.not_success(n_dst, **kw)


# ---------------------------------------------------------------------------
# One-shot Monte-Carlo samplers
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n", "trials", "width"))
def _sample_boolean_kernel(key, table, n: int, trials: int, width: int):
    kb, ks = jax.random.split(key)
    bits = jax.random.bernoulli(kb, 0.5, (n, trials, width))
    k = jnp.sum(bits.astype(jnp.int32), axis=0)          # (T, W) popcounts
    p_ok = table[k]
    ok = jax.random.uniform(ks, (trials, width)) < p_ok
    return jnp.mean(ok)


def sample_boolean_success(op: str, n: int, *, trials: int = 10_000,
                           width: int = 1024, seed: int = 0, **kw) -> float:
    """Cell-averaged MC success of the closed-form model, one jitted call.

    Draws ``trials`` random operand words of ``width`` columns, resolves
    every (trial, column) against the success table, returns the mean —
    the software twin of the paper's 10k-trial protocol.
    """
    table = boolean_success_table(op, n, **kw)
    key = jax.random.PRNGKey(seed)
    return float(_sample_boolean_kernel(key, table, n, trials, width))


@functools.partial(jax.jit, static_argnames=("trials", "width"))
def _sample_not_kernel(key, p_ok, trials: int, width: int):
    ok = jax.random.uniform(key, (trials, width)) < p_ok
    return jnp.mean(ok)


def sample_not_success(n_dst: int = 1, *, trials: int = 10_000,
                       width: int = 1024, seed: int = 0, **kw) -> float:
    """MC estimate of NOT success from the closed-form model, one call."""
    p_ok = A.not_success(n_dst, **kw)
    key = jax.random.PRNGKey(seed)
    return float(_sample_not_kernel(key, p_ok, trials, width))
