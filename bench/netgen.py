"""The benchmark's connectome: a FlyWire-statistics graph made on the device
from the run's seed, in one jitted call.

The statistics follow the synthetic FlyWire model the configuration files
state (log-normal out-degree with a few large-fan-out outliers,
preferential-attachment targets with a few large-fan-in hubs, integer
weights dominated by +-1 with a geometric body and rare large outliers,
Dale's law per source).  Two properties are fixed for every seed, so that
one compiled program serves every run of a cell:

* the synapse count is exactly ``n_synapses`` (duplicate source-target
  pairs are kept as separate synapses, never merged);
* each of ``target_strata`` contiguous, equal neuron ranges receives
  exactly ``n_synapses / target_strata`` synapses, so a partitioned store
  cut along those ranges has the same size for every seed.

The arrays come back to the host once, as the source-major and
target-major CSR tables the simulator and the reference both read.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

MAX_FAN_OUT = 9_783        # FlyWire's largest fan-out
W_MAX_EXC = 1_897          # FlyWire's weight range
W_MAX_INH = 2_405


@dataclasses.dataclass(frozen=True)
class Network:
    """Host CSR tables of one generated connectome (int32 / int64)."""

    n: int
    out_indptr: np.ndarray    # [n+1] int64, fan-out rows (source-major)
    out_indices: np.ndarray   # [nnz] int32 target per synapse
    out_weights: np.ndarray   # [nnz] int32 signed weight
    in_indptr: np.ndarray     # [n+1] int64, fan-in rows (target-major)
    in_indices: np.ndarray    # [nnz] int32 source per synapse
    in_weights: np.ndarray    # [nnz] int32

    @property
    def nnz(self) -> int:
        return int(self.out_indices.shape[0])


def strata_offsets(n: int, strata: int) -> np.ndarray:
    """Contiguous near-equal neuron ranges, the larger ones first."""
    sizes = np.full(strata, n // strata, np.int64)
    sizes[: n % strata] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _generate(key, n: int, nnz: int, strata: int, spec: tuple):
    (sigma, frac_inh, frac_pm1, geom_p, outlier_p,
     outlier_lo, hub_boost) = spec
    ks = jax.random.split(key, 12)
    mean_deg = nnz / n

    # out-degree: log-normal, a few heavy outliers, at least one synapse
    # per source, scaled to exactly nnz by largest remainders
    mu = np.log(mean_deg) - sigma ** 2 / 2
    x = jnp.exp(mu + sigma * jax.random.normal(ks[0], (n,)))
    hi = min(0.07 * n, MAX_FAN_OUT)
    n_hi = max(1, n // 2000)
    big = jax.random.choice(ks[1], n, (n_hi,), replace=False)
    x = x.at[big].set(jax.random.uniform(ks[2], (n_hi,), minval=0.5 * hi,
                                         maxval=hi))
    x = jnp.clip(x, 1.0, hi)
    share = x / jnp.sum(x) * (nnz - n)
    deg = jnp.floor(share).astype(jnp.int32)
    short = (nnz - n) - jnp.sum(deg)
    rank = jnp.argsort(jnp.argsort(-(share - deg)))
    deg = 1 + deg + (rank < short).astype(jnp.int32)
    out_indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(deg).astype(jnp.int32)])
    pre = jnp.repeat(jnp.arange(n, dtype=jnp.int32), deg,
                     total_repeat_length=nnz)

    # targets: preferential attachment inside each stratum; every stratum
    # receives exactly nnz / strata synapses, in random synapse order
    off = strata_offsets(n, strata)
    stratum_of = jnp.asarray(np.repeat(np.arange(strata), np.diff(off)))
    attract = jnp.exp(jax.random.normal(ks[3], (n,)))
    hubs = jax.random.choice(ks[4], n, (max(1, n // 2000),), replace=False)
    attract = attract.at[hubs].multiply(hub_boost)
    csum = jnp.cumsum(attract)
    first = jnp.asarray(off[:-1])
    base = jnp.where(first > 0, csum[jnp.maximum(first - 1, 0)], 0.0)
    total = csum[jnp.asarray(off[1:] - 1)] - base
    local_cdf = (csum - base[stratum_of]) / total[stratum_of]
    cdf = stratum_of.astype(jnp.float32) + local_cdf
    label = jax.random.permutation(
        ks[5], jnp.arange(nnz, dtype=jnp.int32) // (nnz // strata))
    u = label.astype(jnp.float32) + jax.random.uniform(ks[6], (nnz,))
    post = jnp.searchsorted(cdf, u, side="right").astype(jnp.int32)
    lo = jnp.asarray(off[:-1], jnp.int32)[label]
    size = jnp.asarray(np.diff(off), jnp.int32)[label]
    post = jnp.clip(post, lo, lo + size - 1)
    # no self-synapses: step to the next neuron inside the same stratum
    post = jnp.where(post == pre, lo + (post - lo + 1) % size, post)

    # weights: |w| = 1 with prob frac_pm1, else 1 + Geometric(geom_p), rare
    # large outliers; one sign per source (Dale's law)
    tail = jax.random.uniform(ks[7], (nnz,)) >= frac_pm1
    body = 1 + jax.random.geometric(ks[8], geom_p, (nnz,)).astype(jnp.int32)
    mag = jnp.where(tail, body, 1)
    rare = jax.random.uniform(ks[9], (nnz,)) < outlier_p
    mag = jnp.where(rare, jax.random.randint(ks[10], (nnz,), outlier_lo,
                                              W_MAX_EXC), mag)
    inh = (jax.random.uniform(ks[11], (n,)) < frac_inh)[pre]
    w = jnp.where(inh, -jnp.minimum(mag, W_MAX_INH),
                  jnp.minimum(mag, W_MAX_EXC)).astype(jnp.int32)

    # target-major view: a stable sort of the synapses by target
    order = jnp.argsort(post, stable=True)
    in_deg = jnp.zeros(n, jnp.int32).at[post].add(1)
    in_indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(in_deg).astype(jnp.int32)])
    return out_indptr, post, w, in_indptr, pre[order], w[order]


def generate(spec: dict, seed: int) -> Network:
    """The connectome a configuration's ``network`` block describes, drawn
    from ``seed`` on the default device and copied to the host."""
    n, nnz, strata = (int(spec["n_neurons"]), int(spec["n_synapses"]),
                      int(spec["target_strata"]))
    if nnz % strata or nnz < n:
        raise ValueError(f"n_synapses={nnz} must be >= n_neurons={n} and a "
                         f"multiple of target_strata={strata}")
    params = (float(spec["out_degree_sigma"]), float(spec["frac_inhibitory"]),
              float(spec["frac_pm1"]), float(spec["weight_geometric_p"]),
              float(spec["weight_outlier_p"]), int(spec["weight_outlier_min"]),
              float(spec["hub_attract_boost"]))
    out = _generate(jax.random.PRNGKey(seed), n, nnz, strata, params)
    oi, ot, ow, ii, isrc, iw = jax.device_get(out)
    return Network(n=n, out_indptr=oi.astype(np.int64), out_indices=ot,
                   out_weights=ow, in_indptr=ii.astype(np.int64),
                   in_indices=isrc, in_weights=iw)


__all__ = ["Network", "generate", "strata_offsets"]
