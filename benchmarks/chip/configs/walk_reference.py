"""Plain reference of a streaming walk store: the semantics the deepwalk-er
and node2vec-er deployments state, in NumPy on the host.

It imports nothing of the program. It takes the edges the traffic
generator drew (never the program's graph) and builds the edge set a
stream of batches leaves: each batch deletes, then inserts, both
directions of each edge, without duplicates (paper §3.1). It reads the
program's stored corpus only as the answer under test, and decodes it with
its own Szudzik inverse (an integer square root from a float64 estimate
and exact integer corrections; no code shared with the kernels).

The guarantees checked, each as a count that must be 0:

  graph_edges_differ     edges in one of the program's graph and the
                         reference's, not the other
  graph_offsets_differ   vertices whose CSR bounds disagree with the
                         reference's degrees
  slots_not_once         corpus slots (w, p) without exactly one live
                         triplet; live = the newest epoch stored for that
                         slot, base and pending together
  slot_epoch_differ      slots whose stored slot_epoch is not that newest
                         epoch
  pending_slot_differ    pending entries whose explicit slot disagrees with
                         the slot their code decodes to
  chain_breaks           triplets whose next vertex is not the owner of the
                         walk's next slot (the terminal one points to
                         itself)
  starts_differ          walks that do not start at w // n_w
  invalid_transitions    consecutive vertices (a, b) of a walk that are not
                         an edge of the reference graph (an isolated vertex
                         may step to itself)
  store_order_violations base rows out of (owner, code) order, plus
                         vertices whose segment bounds (offsets) disagree
                         with the owners
  stale_slots            live slots whose epoch predates the last batch
                         that changed their owner's adjacency: a walk that
                         visits a vertex whose neighbours a batch changed
                         is rewalked from its first such position, so every
                         slot from there on carries that batch's epoch
                         (batch k writes epoch k; the built corpus epoch 0)

and the distribution the walk model states, each as |z| of an observed
count against its expectation on the final graph, under a limit (LIMITS)
set from sound runs and a control. Every stored step was sampled on a graph
in which its own vertices had their current neighbours (stale_slots holds
that), so the final graph gives each step's law:

  uniform_chi2_z         order 1: Pearson's chi-square of the step counts
                         per (vertex, neighbour) against uniform over the
                         vertex's neighbours, standardised by its exact
                         mean and variance given each vertex's step count
  return_share_z         order 2: steps back to the previous vertex,
  common_share_z         and steps to a common neighbour of the previous
                         and current vertex, against node2vec(p, q): exact
                         where both degrees are at most dmax, else the
                         K-trial rejection sampler's law (its last proposal
                         kept when all K are refused)

CONTROLS are the reference walking in the program's place with the model's
guarantee broken, on the same final graph: `bf16_draw` picks DeepWalk's
neighbour from a uniform rounded to bfloat16, `uniform_order2` walks
node2vec's corpus with p = q = 1.
"""
from __future__ import annotations

import numpy as np

U64 = np.uint64
PAD_EPOCH = 0xFFFFFFFF
# limits of the distribution numbers (|z|), each between the most that
# sound runs read over a dozen seeds and the least that its control reads,
# at the cells' own sizes on the chip (PERF.md 4.3); every count has limit 0
LIMITS = {"uniform_chi2_z": 16.0, "return_share_z": 16.0,
          "common_share_z": 30.0}
SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)
_M32 = np.uint64(0xFFFFFFFF)


# ------------------------------------------------------------------ pairing


def isqrt(z: np.ndarray) -> np.ndarray:
    """floor(sqrt(z)) for uint64 z, exact: a float64 estimate is within one
    of the root for every z < 2**64; integer steps settle it."""
    z = np.asarray(z, U64)
    r = np.minimum(np.sqrt(z.astype(np.float64)).astype(U64), _M32)
    for _ in range(2):
        r = np.where(r * r > z, r - U64(1), r)
    for _ in range(2):
        up = r + U64(1)
        r = np.where((r < _M32) & (up * up <= z), up, r)
    return r


def unpair(z: np.ndarray) -> tuple:
    """Szudzik's inverse: z -> (x, y) with z = y*y + x (x < y) or
    x*x + x + y (x >= y)."""
    z = np.asarray(z, U64)
    s = isqrt(z)
    rem = z - s * s
    lt = rem < s
    return np.where(lt, rem, s), np.where(lt, s, rem - s)


def pair(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    x = np.asarray(x, U64)
    y = np.asarray(y, U64)
    return np.where(x < y, y * y + x, x * x + x + y)


# -------------------------------------------------------------------- graph


def _both(src, dst) -> np.ndarray:
    s = np.asarray(src, U64).ravel()
    d = np.asarray(dst, U64).ravel()
    return np.unique(np.concatenate([(s << U64(32)) | d, (d << U64(32)) | s]))


def replay(base_src, base_dst, batches, n: int) -> tuple:
    """(edges, last_changed): the sorted directed edge codes (src << 32 |
    dst) after the base edges and each (ins_src, ins_dst, del_src, del_dst)
    batch in order, and per vertex the number (from 1) of the last batch
    that changed its neighbours, 0 where none did."""
    edges = _both(base_src, base_dst)
    last = np.zeros(n, np.int64)
    for k, (ins_s, ins_d, del_s, del_d) in enumerate(batches, 1):
        before = edges
        if np.size(del_s):
            edges = np.setdiff1d(edges, _both(del_s, del_d),
                                 assume_unique=True)
        edges = np.union1d(edges, _both(ins_s, ins_d))
        # both directions are stored, so the sources name every endpoint
        changed = np.setxor1d(before, edges, assume_unique=True)
        last[(changed >> U64(32)).astype(np.int64)] = k
    return edges, last


def degrees(edges: np.ndarray, n: int) -> np.ndarray:
    return np.bincount((edges >> U64(32)).astype(np.int64), minlength=n)


def check_graph(edges: np.ndarray, n: int, g_codes, g_num, g_offsets) -> dict:
    codes = np.asarray(g_codes, U64)
    num = int(g_num)
    live = codes[:num]
    differ = (np.setxor1d(live, edges).size
              + int(np.count_nonzero(codes[num:] != SENTINEL)))
    offsets = np.concatenate([[0], np.cumsum(degrees(edges, n))])
    return {"graph_edges_differ": int(differ),
            "graph_offsets_differ": int(np.count_nonzero(
                np.asarray(g_offsets, np.int64) != offsets))}


# ------------------------------------------------------------------- corpus


def decode_corpus(store: dict, pending: dict, n_walks: int,
                  length: int) -> tuple:
    """(walks [n_walks, length] u32, epochs [n_walks, length] (-1: no
    entry), counts) from a stored corpus: base rows plus pending rows, each
    slot resolved to its newest epoch.

    `store` holds owner, code, epoch, slot_epoch (flat arrays); `pending`
    owner, code, epoch, slot (any shape)."""
    p_epoch = np.asarray(pending["epoch"]).ravel()
    keep = p_epoch != PAD_EPOCH
    owner = np.concatenate([np.asarray(store["owner"]).ravel(),
                            np.asarray(pending["owner"]).ravel()[keep]])
    code = np.concatenate([np.asarray(store["code"], U64).ravel(),
                           np.asarray(pending["code"], U64).ravel()[keep]])
    epoch = np.concatenate([np.asarray(store["epoch"]).ravel(),
                            p_epoch[keep]]).astype(np.int64)
    n_base = np.asarray(store["code"]).size
    slots = n_walks * length
    f, nxt = unpair(code)
    counts = {}
    in_range = f < U64(slots)
    counts["slots_out_of_range"] = int(np.count_nonzero(~in_range))
    p_slot = np.asarray(pending["slot"]).ravel()[keep].astype(np.int64)
    counts["pending_slot_differ"] = int(np.count_nonzero(
        f[n_base:].astype(np.int64) != p_slot))
    f = f[in_range].astype(np.int64)
    owner, nxt, epoch = owner[in_range], nxt[in_range], epoch[in_range]
    newest = np.full(slots, -1, np.int64)
    np.maximum.at(newest, f, epoch)
    live = epoch == newest[f]
    per_slot = np.bincount(f[live], minlength=slots)
    counts["slots_not_once"] = int(np.count_nonzero(per_slot != 1))
    slot_epoch = np.asarray(store["slot_epoch"]).ravel().astype(np.int64)
    counts["slot_epoch_differ"] = int(np.count_nonzero(
        (newest >= 0) & (slot_epoch != newest)))
    walks = np.zeros(slots, np.uint32)
    nexts = np.zeros(slots, np.uint32)
    walks[f[live]] = owner[live]
    nexts[f[live]] = nxt[live].astype(np.uint32)
    walks = walks.reshape(n_walks, length)
    nexts = nexts.reshape(n_walks, length)
    counts["chain_breaks"] = int(
        np.count_nonzero(nexts[:, :-1] != walks[:, 1:])
        + np.count_nonzero(nexts[:, -1] != walks[:, -1]))
    return walks, newest.reshape(n_walks, length), counts


def stale_slots(walks: np.ndarray, epochs: np.ndarray,
                last_changed: np.ndarray) -> dict:
    held = epochs >= 0
    return {"stale_slots": int(np.count_nonzero(
        held & (epochs < last_changed[walks.astype(np.int64)])))}


def check_walks(walks: np.ndarray, n_w: int, edges: np.ndarray,
                n: int) -> dict:
    n_walks = walks.shape[0]
    starts = np.arange(n_walks, dtype=np.int64) // n_w
    a = walks[:, :-1].ravel().astype(U64)
    b = walks[:, 1:].ravel().astype(U64)
    q = (a << U64(32)) | b
    pos = np.clip(np.searchsorted(edges, q), 0, max(edges.size - 1, 0))
    in_graph = edges[pos] == q if edges.size else np.zeros(q.shape, bool)
    isolated = degrees(edges, n)[a.astype(np.int64)] == 0
    ok = in_graph | ((a == b) & isolated)
    return {"starts_differ": int(np.count_nonzero(walks[:, 0] != starts)),
            "invalid_transitions": int(np.count_nonzero(~ok))}


def check_store_order(store: dict, n: int) -> dict:
    owner = np.asarray(store["owner"]).ravel().astype(np.int64)
    code = np.asarray(store["code"], U64).ravel()
    same = owner[1:] == owner[:-1]
    bad = np.count_nonzero(owner[1:] < owner[:-1]) + np.count_nonzero(
        same & (code[1:] < code[:-1]))
    offsets = np.searchsorted(owner, np.arange(n + 1), side="left")
    bad += np.count_nonzero(np.asarray(store["offsets"]).ravel() != offsets)
    return {"store_order_violations": int(bad)}


def check_state(state: dict, edges: np.ndarray, last_changed: np.ndarray,
                cfg: dict) -> tuple:
    """Every number above for one engine state (host arrays: graph codes,
    num_edges, offsets; store; pending), against the reference graph and
    each vertex's last change (`replay`). Returns (walks, numbers)."""
    n = 1 << cfg["log2_n"]
    n_w, length = cfg["n_walks_per_vertex"], cfg["length"]
    g = state["graph"]
    counts = check_graph(edges, n, g["codes"], g["num_edges"], g["offsets"])
    walks, epochs, c = decode_corpus(state["store"], state["pending"],
                                     n * n_w, length)
    counts.update(c)
    counts.update(stale_slots(walks, epochs, last_changed))
    counts.update(check_walks(walks, n_w, edges, n))
    counts.update(check_store_order(state["store"], n))
    counts.update(check_distribution(walks, edges, cfg))
    return walks, counts


# ------------------------------------------------------------ distribution


def _csr(edges: np.ndarray, n: int) -> tuple:
    deg = degrees(edges, n)
    off = np.concatenate([[0], np.cumsum(deg)])
    return deg, off, (edges & _M32).astype(np.int64)


def _has_edge(edges: np.ndarray, a, b) -> np.ndarray:
    q = (np.asarray(a, U64) << U64(32)) | np.asarray(b, U64)
    if not edges.size:
        return np.zeros(q.shape, bool)
    pos = np.clip(np.searchsorted(edges, q), 0, edges.size - 1)
    return edges[pos] == q


def uniform_chi2_z(walks: np.ndarray, edges: np.ndarray, n: int) -> float:
    """Pearson's chi-square of the steps out of each vertex (of degree 2 or
    more) over its neighbours against uniform, minus its mean, over its
    standard deviation. Given n_a steps out of a vertex of degree d, the
    statistic has mean d - 1 and variance 2 (d - 1)(n_a - 1) / n_a
    exactly."""
    a = walks[:, :-1].ravel().astype(np.int64)
    b = walks[:, 1:].ravel().astype(np.int64)
    deg = degrees(edges, n)
    keep = _has_edge(edges, a, b) & (deg[a] >= 2)
    codes, cnt = np.unique((a[keep].astype(U64) << U64(32))
                           | b[keep].astype(U64), return_counts=True)
    src = (codes >> U64(32)).astype(np.int64)
    cnt = cnt.astype(np.float64)
    n_a = np.bincount(src, weights=cnt, minlength=n)
    s2 = np.bincount(src, weights=cnt * cnt, minlength=n)
    m = n_a > 0
    d, n_a, s2 = deg[m].astype(np.float64), n_a[m], s2[m]
    x2 = np.sum(d / n_a * s2 - n_a)
    var = np.sum(2.0 * (d - 1.0) * (n_a - 1.0) / n_a)
    return float((x2 - np.sum(d - 1.0)) / np.sqrt(var)) if var > 0 else 0.0


def _common_counts(edges: np.ndarray, n: int, a, b,
                   chunk: int = 1 << 22) -> np.ndarray:
    """|N(a) & N(b) - {a}| for each pair (a, b)."""
    deg, off, nbr = _csr(edges, n)
    out = np.zeros(len(a), np.int64)
    start = 0
    while start < len(a):
        # as many pairs as expand to about `chunk` neighbour entries
        cum = np.cumsum(deg[b[start:]])
        stop = start + max(1, int(np.searchsorted(cum, chunk)))
        aa, bb = a[start:stop], b[start:stop]
        rep = deg[bb]
        row = np.repeat(np.arange(len(bb)), rep)
        within = np.arange(rep.sum()) - np.repeat(np.cumsum(rep) - rep, rep)
        x = nbr[off[bb][row] + within]
        hit = _has_edge(edges, aa[row], x) & (x != aa[row])
        out[start:stop] = np.bincount(row, weights=hit,
                                      minlength=len(bb)).astype(np.int64)
        start = stop
    return out


def node2vec_z(walks: np.ndarray, edges: np.ndarray, n: int,
               model: dict) -> dict:
    """z of the return and common-neighbour step counts (steps t >= 1,
    from b = x[t] with previous a = x[t-1] to x[t+1]) against their
    expected sums under the program's order-2 sampler."""
    inv_p, inv_q = 1.0 / model["p"], 1.0 / model["q"]
    dmax, k = model["dmax"], model["n_trials"]
    a = walks[:, :-2].ravel().astype(np.int64)
    b = walks[:, 1:-1].ravel().astype(np.int64)
    x = walks[:, 2:].ravel().astype(np.int64)
    deg = degrees(edges, n)
    keep = deg[b] > 0
    a, b, x = a[keep], b[keep], x[keep]
    pair, inv = np.unique(a.astype(U64) << U64(32) | b.astype(U64),
                          return_inverse=True)
    pa = (pair >> U64(32)).astype(np.int64)
    pb = (pair & _M32).astype(np.int64)
    c0 = _has_edge(edges, pb, pa).astype(np.float64)[inv]
    c1 = _common_counts(edges, n, pa, pb).astype(np.float64)[inv]
    d = deg[b].astype(np.float64)
    c2 = d - c0 - c1
    mass = c0 * inv_p + c1 + c2 * inv_q
    # the factorized sampler: exact group masses
    p_ret, p_com = c0 * inv_p / mass, c1 / mass
    # the rejection fallback: K uniform proposals, each accepted with
    # alpha / alpha_max; the first accepted one, or else the last proposal
    amax = max(inv_p, 1.0, inv_q)
    acc = mass / (d * amax)
    s = (1.0 - (1.0 - acc) ** k) / acc
    t = (1.0 - acc) ** (k - 1)
    r_ret = c0 / d * (inv_p / amax * s + t * (1.0 - inv_p / amax))
    r_com = c1 / d * (1.0 / amax * s + t * (1.0 - 1.0 / amax))
    over = (deg[b] > dmax) | (deg[a] > dmax)
    p_ret = np.where(over, r_ret, p_ret)
    p_com = np.where(over, r_com, p_com)
    ret = x == a
    com = ~ret & _has_edge(edges, a, x)
    out = {}
    for name, obs, prob in (("return_share_z", ret, p_ret),
                            ("common_share_z", com, p_com)):
        var = np.sum(prob * (1.0 - prob))
        out[name] = (float((np.count_nonzero(obs) - np.sum(prob))
                           / np.sqrt(var)) if var > 0 else 0.0)
    return out


def check_distribution(walks: np.ndarray, edges: np.ndarray,
                       cfg: dict) -> dict:
    """The walk model's distribution numbers, each as |z|."""
    n = 1 << cfg["log2_n"]
    model = cfg["walk_model"]
    if model["order"] == 1:
        z = {"uniform_chi2_z": uniform_chi2_z(walks, edges, n)}
    else:
        z = node2vec_z(walks, edges, n, model)
    return {k: abs(v) for k, v in z.items()}


def bf16_round(u: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (8 significant bits), nearest
    even."""
    bits = np.asarray(u, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def uniform_walks(edges: np.ndarray, cfg: dict, rng,
                  draw: str = "float64") -> np.ndarray:
    """Walks from w // n_w, each step to a neighbour drawn as
    floor(u * degree) (an isolated vertex stays), u uniform in float64, or
    rounded to bfloat16 (`draw="bfloat16"`)."""
    n = 1 << cfg["log2_n"]
    n_w, length = cfg["n_walks_per_vertex"], cfg["length"]
    deg, off, nbr = _csr(edges, n)
    cur = np.arange(n * n_w, dtype=np.int64) // n_w
    walks = np.empty((n * n_w, length), np.uint32)
    walks[:, 0] = cur
    for t in range(1, length):
        u = rng.random(cur.shape)
        if draw == "bfloat16":
            u = bf16_round(u.astype(np.float32)).astype(np.float64)
        d = deg[cur]
        i = np.minimum(np.floor(u * d).astype(np.int64),
                       np.maximum(d - 1, 0))
        cur = np.where(d > 0, nbr[np.minimum(off[cur] + i, nbr.size - 1)],
                       cur)
        walks[:, t] = cur
    return walks


# each control: (the walk model it breaks, how its walks are drawn)
CONTROLS = {"bf16_draw": (1, "bfloat16"), "uniform_order2": (2, "float64")}


def control_numbers(name: str, edges: np.ndarray, cfg: dict,
                    seed: int) -> dict:
    """The distribution numbers of a control's corpus on the final graph,
    or {} where the control is for the other walk model."""
    order, draw = CONTROLS[name]
    if cfg["walk_model"]["order"] != order:
        return {}
    walks = uniform_walks(edges, cfg, np.random.default_rng(seed), draw)
    return check_distribution(walks, edges, cfg)


# -------------------------------------------------------------------- reads


def visits(walks: np.ndarray, n: int) -> tuple:
    """Index of walk visits by vertex: (order, bounds); the walks visiting v
    are order[bounds[v]:bounds[v + 1]] // length, one per visit."""
    flat = walks.ravel().astype(np.int64)
    order = np.argsort(flat, kind="stable")
    bounds = np.concatenate([[0], np.cumsum(np.bincount(flat, minlength=n))])
    return order, bounds


def next_vertex_wrong(walks: np.ndarray, w, p, v_next, found) -> int:
    """Answers of next_vertices(v, w, p) (v = walks[w, p]) that are not
    found or are not walks[w, p + 1]."""
    want = walks[np.asarray(w, np.int64), np.asarray(p, np.int64) + 1]
    return int(np.count_nonzero(~np.asarray(found, bool)
                                | (np.asarray(v_next) != want)))


def walks_of_wrong(index: tuple, length: int, vertices, rows) -> int:
    """Rows of walks_of(vertices) (walk ids, -1 padded, one per visit)
    whose multiset of walk ids differs from the reference's."""
    order, bounds = index
    wrong = 0
    for v, row in zip(np.asarray(vertices, np.int64), np.asarray(rows)):
        want = np.sort(order[bounds[v]:bounds[v + 1]] // length)
        got = np.sort(row[row >= 0].astype(np.int64))
        wrong += int(want.shape != got.shape or np.any(want != got))
    return wrong
