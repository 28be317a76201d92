"""The general traffic generator: every mix is a data file of parameters
(traffic/<name>.json) that this module reads. Everything is drawn from the
run's seed, so the same seed gives the same graph, batches and requests.

Graphs and update batches are R-MAT (paper §7.1): a copy of the program's
generator, kept here so that the yardstick cannot move with the program.

  er-k base graphs     a = b = c = d = .25, average degree 100 (TrillionG)
  update batches       a = .5, b = c = .1, d = .3 (as in Aspen), inserts
                       and deletes drawn from the same distribution

Two request kinds exist; a mix names one in `requests`:

  update_batches   closed loop: batch i + 1 is sent when batch i is
                   written. Parameters: batch_inserts, batch_deletes,
                   rmat, setup_batches, max_window_batches.
  point_reads      open loop: Poisson arrivals at rate_per_s, each request
                   one query batch of queries_per_request, kinds drawn by
                   `mix`. A set-up update batch (setup_update) writes the
                   pending version block the reads go through.
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

U32_MAX = 0xFFFFFFFF


def seed_words(seed: int) -> tuple:
    """Any whole number (seeds may pass 2**31) as two u32 words."""
    s = int(seed) % (1 << 64)
    return s & U32_MAX, s >> 32


def key(seed: int, salt: int):
    """A JAX PRNG key for one stream of the run, from the seed and a salt."""
    import jax
    lo, hi = seed_words(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(k, salt)


def host_rng(seed: int, salt: int) -> np.random.Generator:
    lo, hi = seed_words(seed)
    return np.random.default_rng([lo, hi, salt])


@lru_cache(maxsize=None)
def _rmat_jit():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("n_edges", "log2_n"))
    def rmat_edges(key, n_edges: int, log2_n: int, a, b, c, d):
        """n_edges (src, dst) pairs from R-MAT over 2^log2_n vertices."""
        probs = jnp.stack([a, b, c, d])
        keys = jax.random.split(key, log2_n)

        def level(carry, k):
            src, dst = carry
            q = jax.random.categorical(k, jnp.log(probs), shape=(n_edges,))
            src = (src << 1) | (q >= 2).astype(jnp.uint32)
            dst = (dst << 1) | (q % 2).astype(jnp.uint32)
            return (src, dst), None

        z = jnp.zeros((n_edges,), jnp.uint32)
        (src, dst), _ = jax.lax.scan(level, (z, z), keys)
        return src, dst

    @partial(jax.jit, static_argnames=("n_batches", "n_edges", "log2_n"))
    def rmat_batches(key, n_batches: int, n_edges: int, log2_n: int,
                     a, b, c, d):
        keys = jax.random.split(key, n_batches)
        return jax.vmap(lambda k: rmat_edges(k, n_edges, log2_n,
                                             a, b, c, d))(keys)

    return rmat_edges, rmat_batches


def _probs(p: dict):
    import jax.numpy as jnp
    return tuple(jnp.asarray(p[x], jnp.float32) for x in "abcd")


def base_graph_edges(cfg: dict, seed: int):
    """The configuration's base graph: n * avg_degree / 2 R-MAT samples
    (undirected), as device arrays (src, dst) u32."""
    rmat_edges, _ = _rmat_jit()
    n_edges = (1 << cfg["log2_n"]) * cfg["avg_degree"] // 2
    return rmat_edges(key(seed, 1), n_edges, cfg["log2_n"],
                      *_probs(cfg["base_graph"]["rmat"]))


def update_batches(mix: dict, log2_n: int, n_batches: int, seed: int,
                   salt: int = 2) -> dict:
    """n_batches mixed batches as host arrays: ins_src, ins_dst
    [n, batch_inserts], del_src, del_dst [n, batch_deletes] (u32), and one
    PRNG key per batch for the engine's draws, keys [n, 2] (u32)."""
    import jax
    _, rmat_batches = _rmat_jit()
    k_ins, k_del, k_walk = (key(seed, salt * 16 + i) for i in range(3))
    p = _probs(mix["rmat"])
    ins = rmat_batches(k_ins, n_batches, mix["batch_inserts"], log2_n, *p)
    dels = rmat_batches(k_del, n_batches, max(mix["batch_deletes"], 1),
                        log2_n, *p)
    keys = jax.random.split(k_walk, n_batches)
    out = jax.device_get(dict(ins_src=ins[0], ins_dst=ins[1],
                              del_src=dels[0], del_dst=dels[1], keys=keys))
    nd = mix["batch_deletes"]
    out["del_src"] = out["del_src"][:, :nd]
    out["del_dst"] = out["del_dst"][:, :nd]
    return {k: np.ascontiguousarray(v) for k, v in out.items()}


def batch(batches: dict, i: int) -> tuple:
    """Batch i as run_stream takes it: (key, ins_src, ins_dst, del_src,
    del_dst), each with a leading batch axis of 1."""
    return (batches["keys"][i],) + tuple(
        batches[k][i:i + 1] for k in ("ins_src", "ins_dst", "del_src",
                                      "del_dst"))


def batch_edges(mix: dict) -> int:
    """Edge updates one batch sends (inserts plus deletes)."""
    return mix["batch_inserts"] + mix["batch_deletes"]


# ------------------------------------------------------------- point reads


def read_schedule(mix: dict, seconds: float, seed: int) -> dict:
    """Due times (seconds after the window opens) and kinds of every
    request due in the window, at rate_per_s.

    The gaps between arrivals are exponential, as in a Poisson process,
    but stratified: every seed gets the same set of gaps (the exponential
    quantiles at (i + 1/2) / n for the window's n = rate x seconds
    requests) and the same count of each kind, in an order drawn from the
    seed. So seeds change which requests queue behind which, not how much
    work arrives."""
    rng = host_rng(seed, 3)
    rate = float(mix["rate_per_s"])
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / max(n, 1)) / rate
    due = np.cumsum(rng.permutation(gaps))
    due = due[due < seconds]
    kinds = list(mix["mix"])
    share = np.asarray([mix["mix"][k] for k in kinds], np.float64)
    counts = np.floor(share / share.sum() * due.shape[0]).astype(int)
    counts[0] += due.shape[0] - counts.sum()
    pick = rng.permutation(np.repeat(np.arange(len(kinds)), counts))
    return dict(due=due, kind=[kinds[i] for i in pick])


def rmat_vertices(rng: np.random.Generator, n: int, log2_n: int,
                  p: dict) -> np.ndarray:
    """Vertices drawn as R-MAT edge sources: bit 1 at each level with
    probability c + d, so the update skew's hubs are asked for more."""
    one = p["c"] + p["d"]
    bits = rng.random((n, log2_n)) < one
    weights = 1 << np.arange(log2_n - 1, -1, -1, dtype=np.uint64)
    return (bits.astype(np.uint64) @ weights).astype(np.uint32)


def read_requests(mix: dict, schedule: dict, walks: np.ndarray,
                  n_w: int, seed: int) -> list:
    """The query arrays of every request in the schedule.

    next_vertices: (v, w, p) with w uniform over the corpus, p uniform over
    every position but the last, and v the walk's vertex at p in `walks`
    (the decoded
    corpus the reads run against). walks_of: vertices with the update
    skew."""
    rng = host_rng(seed, 4)
    q = mix["queries_per_request"]
    n_walks, length = walks.shape
    log2_n = int(n_walks // n_w).bit_length() - 1
    out = []
    for kind in schedule["kind"]:
        if kind == "next_vertices":
            w = rng.integers(0, n_walks, q, dtype=np.int64)
            p = rng.integers(0, length - 1, q, dtype=np.int64)
            out.append((w.astype(np.uint32), p.astype(np.uint32),
                        walks[w, p].astype(np.uint32)))
        elif kind == "walks_of":
            out.append(rmat_vertices(rng, q, log2_n,
                                     mix["walks_of_vertices"]["rmat"]))
        else:
            raise ValueError(f"unknown read kind {kind!r}")
    return out
