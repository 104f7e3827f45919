"""Plain reference for the packer's answers, independent of the program.

Everything here is built from a configuration file's numbers (the Table-1
shape rows, the RAM primitives' aspect modes and capacities, the inventory
counts) and imports nothing of the program under test.  It does three
things:

* ``ReferenceProblem.unit_costs`` is the cost model: a bin of width ``w``
  (the widest buffer) and height ``h`` (the summed depths) on RAM kind
  ``k`` takes ``min over k's modes of ceil(w / mode_w) * ceil(h / mode_d)``
  primitives, each worth ``capacity_k / gcd(capacities)`` cost units;
* ``audit`` states what an answer claims and what the reference finds for
  it: every buffer placed exactly once, no bin over ``max_items``, every
  kind in range, the reported cost equal to the cost of the packing, the
  last trace point equal to the penalized cost, and the budget (chain
  steps or generations) spent in full;
* ``replay_sa_s`` anneals the problem again with SA-S (buffer-swap moves,
  per-bin RAM-kind flips, a Lundy-Mees temperature ladder over the chains,
  Metropolis acceptance on the inventory-penalized cost, best-chain
  exchange) from the same seed, drawing the seed's random stream in the
  documented order.  Its answer is the one the packer has to give: the
  same packing, cost and improvement trace;
* ``replay_ga_nfd`` does the same with GA-NFD (NFD seeding, the NFD repack
  as mutation, layer-weighted fitness, tournament selection with elitism)
  on a one-kind problem.

The replay covers the settings the benchmark's cells use (no intra-layer
constraint, patience and wall cap off).  It raises on any other setting
instead of answering for it.
"""
from __future__ import annotations

import math
from functools import reduce

import numpy as np

INVENTORY_PENALTY = 32.0  # cost units per unit of inventory overflow


class ReferenceProblem:
    """One packing problem as the reference sees it."""

    def __init__(self, rows, max_items: int, kinds, counts, name: str = ""):
        widths, depths, layers = [], [], []
        for layer, (n_pe, (n_simd, depth, wbits)) in enumerate(rows):
            widths += [int(n_simd) * int(wbits)] * int(n_pe)
            depths += [int(depth)] * int(n_pe)
            layers += [layer] * int(n_pe)  # a shape row is one layer
        self.name = name
        self.widths = np.asarray(widths, dtype=np.int64)
        self.depths = np.asarray(depths, dtype=np.int64)
        self.layers = layers
        self.widths_py, self.depths_py = widths, depths
        self.n = len(widths)
        self.max_items = int(max_items)
        self.modes = [tuple((int(a), int(b)) for a, b in k["modes"]) for k in kinds]
        caps = [int(k["capacity_bits"]) for k in kinds]
        self.capacity_bits = caps
        unit = reduce(math.gcd, caps)
        self.weights = np.asarray([c // unit for c in caps], dtype=np.int64)
        self.counts = np.asarray(counts, dtype=np.int64)
        self.n_kinds = len(kinds)
        self.bounded = bool((self.counts >= 0).any())
        self._gap_cache: dict = {}

    # ------------------------------------------------------------ cost model
    def primitives(self, w, h, k) -> np.ndarray:
        """Primitive count of bins (w, h) on kinds k; an empty bin (w == 0)
        takes none."""
        w = np.asarray(w, dtype=np.int64)
        h = np.asarray(h, dtype=np.int64)
        k = np.asarray(k, dtype=np.int64)
        out = np.zeros(np.broadcast(w, h, k).shape, dtype=np.int64)
        for ki, modes in enumerate(self.modes):
            best = None
            for mw, md in modes:
                c = -(-w // mw) * -(-h // md)
                best = c if best is None else np.minimum(best, c)
            out = np.where(k == ki, best, out)
        return out

    def unit_costs(self, w, h, k) -> np.ndarray:
        return self.primitives(w, h, k) * self.weights[np.asarray(k, np.int64)]

    def overflow(self, used: np.ndarray) -> np.ndarray:
        """Unit-weighted primitive use beyond the inventory, per row of
        ``used`` (..., n_kinds); unbounded kinds (count < 0) never overflow."""
        over = np.maximum(used - self.counts, 0)
        over = np.where(self.counts < 0, 0, over)
        return (over * self.weights).sum(axis=-1)

    def _gap0(self, w: int, h: int) -> int:
        """Unused rows of a bin on kind 0 under its cheapest mode (the
        first mode of least cost)."""
        key = (w, h)
        hit = self._gap_cache.get(key)
        if hit is None:
            best, best_d = None, 0
            for mw, md in self.modes[0]:
                c = -(-w // mw) * -(-h // md)
                if best is None or c < best:
                    best, best_d = c, md
            hit = self._gap_cache[key] = -(-h // best_d) * best_d - h
        return hit

    def geometry(self, bins) -> tuple[np.ndarray, np.ndarray]:
        ws, ds = self.widths_py, self.depths_py
        w = np.asarray([max(ws[i] for i in b) for b in bins], np.int64)
        h = np.asarray([sum(ds[i] for i in b) for b in bins], np.int64)
        return w, h


# ------------------------------------------------------------------ audit
def audit(prob: ReferenceProblem, bins, kinds, cost, trace_last, iterations,
          expected_iterations, penalty: float = INVENTORY_PENALTY) -> list[str]:
    """What is wrong with one answer, as a list of plain findings (empty when
    the answer holds)."""
    faults = []
    placed = sorted(int(i) for b in bins for i in b)
    if placed != list(range(prob.n)):
        faults.append("not every buffer placed exactly once")
    if any(len(b) == 0 or len(b) > prob.max_items for b in bins):
        faults.append(f"a bin is empty or holds more than {prob.max_items}")
    if len(kinds) != len(bins) or any(k < 0 or k >= prob.n_kinds for k in kinds):
        faults.append("kind lane misaligned or out of range")
    if faults:
        return faults
    w, h = prob.geometry(bins)
    k = np.asarray(kinds, dtype=np.int64)
    true_cost = int(prob.unit_costs(w, h, k).sum())
    if int(cost) != true_cost:
        faults.append(f"reported cost {cost} != {true_cost}")
    used = np.zeros(prob.n_kinds, dtype=np.int64)
    np.add.at(used, k, prob.primitives(w, h, k))
    penalized = true_cost + penalty * int(prob.overflow(used))
    if prob.n_kinds > 1 and float(trace_last) != penalized:
        faults.append(f"last trace point {trace_last} != {penalized}")
    if int(iterations) != int(expected_iterations):
        faults.append(f"{iterations} iterations != {expected_iterations}")
    return faults


def canonical(bins, kinds) -> tuple:
    """Order-free form of a packing: its (buffers, kind) bins, sorted."""
    return tuple(sorted((tuple(sorted(int(i) for i in b)), int(k))
                        for b, k in zip(bins, kinds)))


# ------------------------------------------------------------ NFD seeding
def _nfd_bins(prob: ReferenceProblem, order, rng, p_adm_w, p_adm_h):
    """Next-fit with the NFD admission rule: a buffer joins the open bin
    when the bin has room and the kind-0 grid gap shrinks (or a draw below
    ``p_adm_h`` admits it anyway), and its width matches (or a draw below
    ``p_adm_w`` admits it).  Draws happen only where the rule needs them."""
    widths, depths = prob.widths_py, prob.depths_py
    bins, cur = [], []
    cur_w = cur_h = 0
    for i in order:
        i = int(i)
        w, d = widths[i], depths[i]
        if not cur:
            cur, cur_w, cur_h = [i], w, d
            continue
        new_w, new_h = max(cur_w, w), cur_h + d
        ok = (
            len(cur) < prob.max_items
            and (prob._gap0(new_w, new_h) < prob._gap0(cur_w, cur_h)
                 or rng.random() < p_adm_h)
            and (cur_w == w or rng.random() < p_adm_w)
        )
        if ok:
            cur.append(i)
            cur_w, cur_h = new_w, new_h
        else:
            bins.append(cur)
            cur, cur_w, cur_h = [i], w, d
    if cur:
        bins.append(cur)
    return bins


def _greedy_kinds(prob: ReferenceProblem, bins) -> np.ndarray:
    """Every bin on its cheapest kind; then, while a bounded kind is over
    its count, move the bin with the least cost regret per freed primitive
    to a kind with room."""
    nb, nk = len(bins), prob.n_kinds
    w, h = prob.geometry(bins)
    prim = np.stack([prob.primitives(w, h, np.full(nb, j)) for j in range(nk)], 1)
    wc = prim * prob.weights[None, :]
    kinds = np.argmin(wc, axis=1).astype(np.int64)
    if nk == 1 or not prob.bounded:
        return kinds
    counts = prob.counts
    ar = np.arange(nb)
    used = np.zeros(nk, dtype=np.int64)
    np.add.at(used, kinds, prim[ar, kinds])
    for _ in range(nb + 1):
        over = (counts >= 0) & (used > counts)
        if not over.any():
            break
        cur_wc, cur_prim = wc[ar, kinds], prim[ar, kinds]
        movable = over[kinds] & (cur_prim > 0)
        best = None
        for j in range(nk):
            cand = movable & (kinds != j)
            if counts[j] >= 0:
                cand &= used[j] + prim[:, j] <= counts[j]
            if not cand.any():
                continue
            with np.errstate(divide="ignore", invalid="ignore"):
                regret = np.where(cand, (wc[:, j] - cur_wc) / cur_prim, np.inf)
            bi = int(np.argmin(regret))
            if best is None or regret[bi] < best[0]:
                best = (float(regret[bi]), bi, j)
        if best is None:
            break
        _, bi, j = best
        used[kinds[bi]] -= prim[bi, kinds[bi]]
        kinds[bi] = j
        used[j] += prim[bi, j]
    return kinds


def _nfd_solution(prob, rng, p_adm_w, p_adm_h, sort_by_width):
    order = rng.permutation(prob.n)
    if sort_by_width:
        order = order[np.argsort(prob.widths[order], kind="stable")]
    bins = _nfd_bins(prob, order, rng, p_adm_w, p_adm_h)
    return bins, _greedy_kinds(prob, bins)


# ------------------------------------------------------------ SA-S replay
def replay_sa_s(
    prob: ReferenceProblem,
    seed: int,
    n_chains: int,
    max_iterations: int,
    sa_t0: float = 30.0,
    sa_rc: float = 1.0,
    p_adm_w: float = 0.0,
    p_adm_h: float = 0.1,
    swap_moves: int = 2,
    exchange_every: int = 256,
    ladder_min: float = 0.25,
    ladder_max: float = 4.0,
    p_kind: float = 0.15,
    penalty: float = INVENTORY_PENALTY,
) -> dict:
    """The SA-S answer for ``seed``: ``{"cost", "bins", "kinds", "trace",
    "iterations"}``, with ``trace`` the best penalized cost after each
    improvement (the first point is the best seed packing)."""
    if n_chains < 2:
        raise ValueError("the replay covers the multi-chain annealer only")
    rng = np.random.default_rng(seed)
    C, n_moves = int(n_chains), max(int(swap_moves), 1)
    width = 2 * n_moves
    hetero = prob.n_kinds > 1
    n_u = 6 if hetero else 4
    pk = p_kind if hetero else 0.0
    nk = prob.n_kinds

    sols = [_nfd_solution(prob, rng, p_adm_w, p_adm_h, c % 2 == 1) for c in range(C)]
    nb = max(len(b) for b, _ in sols)
    cap = prob.max_items
    items = np.full((C, nb, cap), -1, dtype=np.int64)
    counts = np.zeros((C, nb), dtype=np.int64)
    bk = np.zeros((C, nb), dtype=np.int64)
    for c, (bins, kinds) in enumerate(sols):
        for b, lst in enumerate(bins):
            items[c, b, : len(lst)] = lst
            counts[c, b] = len(lst)
        bk[c, : len(bins)] = kinds
    sentinel = prob.n
    wtab = np.append(prob.widths, 0)
    dtab = np.append(prob.depths, 0)

    def geom(it_rows):
        ids = np.where(it_rows >= 0, it_rows, sentinel)
        return wtab[ids].max(-1), dtab[ids].sum(-1)

    bw, bh = geom(items)
    live = (counts > 0).sum(1)
    costs = prob.unit_costs(bw, bh, bk).sum(1)
    UK = np.stack([
        np.bincount(bk[c], weights=prob.primitives(bw[c], bh[c], bk[c]),
                    minlength=nk).astype(np.int64)
        for c in range(C)
    ])
    pcosts = costs + penalty * prob.overflow(UK) if hetero else costs.copy()

    t0s = np.full(C, float(sa_t0))
    if C == 2:
        t0s[1] = sa_t0 * math.sqrt(ladder_min * ladder_max)
    else:
        t0s[1:] = sa_t0 * np.geomspace(ladder_min, ladder_max, C - 1)

    g = int(np.argmin(pcosts))
    best = dict(pcost=pcosts[g], cost=costs[g], items=items[g].copy(),
                counts=counts[g].copy(), live=live[g], kinds=bk[g].copy(),
                UK=UK[g].copy())
    trace = [float(best["pcost"]) if hetero else int(best["cost"])]
    ri = np.arange(C)
    rows = ri[:, None]

    for it in range(int(max_iterations)):
        u_all = rng.random((n_moves, n_u, C))
        bk_new = bk.copy()
        tslots = np.zeros((C, width), dtype=np.int64)
        entry_ok = np.zeros((C, width), dtype=bool)
        snaps = []
        for m in range(n_moves):
            u = u_all[m]
            src = np.minimum((u[0] * live).astype(np.int64), live - 1)
            dst = np.minimum((u[1] * live).astype(np.int64), live - 1)
            if hetero:
                kflip = u[4] < pk
                f = np.flatnonzero(kflip)
                shift = 1 + np.minimum((u[5, f] * (nk - 1)).astype(np.int64), nk - 2)
                bk_new[f, src[f]] = (bk_new[f, src[f]] + shift) % nk
            else:
                kflip = np.zeros(C, dtype=bool)
            ok = (live >= 2) & (src != dst) & ~kflip
            cnt_s = counts[ri, src]
            ok &= cnt_s > 0
            item_k = np.minimum((u[2] * cnt_s).astype(np.int64), np.maximum(cnt_s - 1, 0))
            item = items[ri, src, item_k]
            cnt_d = counts[ri, dst]
            full = cnt_d >= cap
            jd = np.minimum((u[3] * cnt_d).astype(np.int64), np.maximum(cnt_d - 1, 0))
            other = items[ri, dst, jd]
            swap = ok & full
            move = ok & ~full
            applied = swap | move
            snaps.append((src, dst, applied, items[ri, src].copy(),
                          items[ri, dst].copy(), cnt_s, cnt_d))
            s = np.flatnonzero(swap)
            items[s, dst[s], jd[s]] = item[s]
            items[s, src[s], item_k[s]] = other[s]
            v = np.flatnonzero(move)
            items[v, src[v], item_k[v]] = items[v, src[v], cnt_s[v] - 1]
            items[v, src[v], cnt_s[v] - 1] = -1
            counts[v, src[v]] -= 1
            items[v, dst[v], cnt_d[v]] = item[v]
            counts[v, dst[v]] += 1
            tslots[:, 2 * m] = src
            tslots[:, 2 * m + 1] = dst
            entry_ok[:, 2 * m] = applied | kflip
            entry_ok[:, 2 * m + 1] = applied
        for a in range(1, width):  # a bin touched twice counts once
            for b in range(a):
                entry_ok[:, a] &= ~(entry_ok[:, b] & (tslots[:, a] == tslots[:, b]))

        sel = np.where(entry_ok, tslots, 0)
        old_w = np.where(entry_ok, bw[rows, sel], 0)
        old_h = np.where(entry_ok, bh[rows, sel], 0)
        old_k = np.where(entry_ok, bk[rows, sel], 0)
        nw, nh = geom(items[rows, sel, :])
        new_w = np.where(entry_ok, nw, 0)
        new_h = np.where(entry_ok, nh, 0)
        new_k = np.where(entry_ok, bk_new[rows, sel], 0)
        d_e = (prob.unit_costs(new_w, new_h, new_k)
               - prob.unit_costs(old_w, old_h, old_k)).sum(1)
        if hetero and prob.bounded:
            po = prob.primitives(old_w, old_h, old_k)
            pn = prob.primitives(new_w, new_h, new_k)
            dUK = np.stack([((new_k == kk) * pn).sum(1) - ((old_k == kk) * po).sum(1)
                            for kk in range(nk)], 1)
            d_tot = d_e + penalty * (prob.overflow(UK + dUK) - prob.overflow(UK))
        else:
            dUK = None
            d_tot = d_e

        temps = t0s / (1.0 + sa_rc * it)
        u_metro = rng.random(C)
        d = np.asarray(d_tot, dtype=np.float64)
        safe_t = np.where(temps > 0, temps, 1.0)
        accept = (d < 0) | ((temps > 0) & (u_metro < np.exp(-np.maximum(d, 0.0) / safe_t)))

        reject = ~accept
        for src, dst, applied, s_items, d_items, s_cnt, d_cnt in reversed(snaps):
            r = np.flatnonzero(reject & applied)
            items[r, dst[r]] = d_items[r]
            counts[r, dst[r]] = d_cnt[r]
            items[r, src[r]] = s_items[r]
            counts[r, src[r]] = s_cnt[r]

        costs = costs + np.where(accept, d_e, 0)
        com = entry_ok & accept[:, None]
        rr, cc = np.nonzero(com)
        bw[rr, tslots[rr, cc]] = new_w[rr, cc]
        bh[rr, tslots[rr, cc]] = new_h[rr, cc]
        if hetero:
            bk = np.where(accept[:, None], bk_new, bk)
            if dUK is not None:
                UK = UK + dUK * accept[:, None]
            pcosts = costs + penalty * prob.overflow(UK)
        else:
            pcosts = costs

        g = int(np.argmin(pcosts))
        if pcosts[g] < best["pcost"]:
            best = dict(pcost=pcosts[g], cost=costs[g], items=items[g].copy(),
                        counts=counts[g].copy(), live=live[g], kinds=bk[g].copy(),
                        UK=UK[g].copy())
            trace.append(float(best["pcost"]) if hetero else int(best["cost"]))

        if exchange_every > 0 and (it + 1) % exchange_every == 0:
            wst = int(np.argmax(pcosts))
            if pcosts[wst] > best["pcost"]:
                items[wst] = best["items"]
                counts[wst] = best["counts"]
                live[wst] = best["live"]
                bw[wst], bh[wst] = geom(best["items"])
                costs[wst] = best["cost"]
                bk[wst] = best["kinds"]
                UK[wst] = best["UK"]
                if hetero:
                    pcosts = costs + penalty * prob.overflow(UK)
            order = np.argsort(counts == 0, axis=1, kind="stable")
            items = np.take_along_axis(items, order[:, :, None], 1)
            counts = np.take_along_axis(counts, order, 1)
            bw = np.take_along_axis(bw, order, 1)
            bh = np.take_along_axis(bh, order, 1)
            bk = np.take_along_axis(bk, order, 1)
            live = (counts > 0).sum(1)

    keep = [b for b in range(nb) if best["counts"][b] > 0]
    return dict(
        cost=int(best["cost"]),
        bins=[[int(x) for x in best["items"][b, : best["counts"][b]]] for b in keep],
        kinds=[int(best["kinds"][b]) for b in keep],
        trace=trace,
        iterations=C * int(max_iterations),
    )


# ---------------------------------------------------------- GA-NFD replay
class _Packing:
    """One individual: its bins, and each bin's cost, mapping efficiency
    (stored bits over the capacity of its primitives) and distinct layers."""

    __slots__ = ("bins", "cost", "eff", "layers")

    def __init__(self, bins, cost, eff, layers):
        self.bins, self.cost, self.eff, self.layers = bins, cost, eff, layers

    @classmethod
    def of(cls, prob: ReferenceProblem, bins) -> "_Packing":
        w, h = prob.geometry(bins)
        zero = np.zeros(len(bins), dtype=np.int64)
        prim = prob.primitives(w, h, zero)
        ws, ds = prob.widths_py, prob.depths_py
        bits = np.asarray([sum(ws[i] * ds[i] for i in b) for b in bins], dtype=np.int64)
        return cls(list(bins), prob.unit_costs(w, h, zero),
                   bits / (prim * float(prob.capacity_bits[0])),
                   np.asarray([len({prob.layers[i] for i in b}) for b in bins],
                              dtype=np.int64))

    def total(self) -> int:
        return int(self.cost.sum())

    def fitness(self, layer_weight: float) -> float:
        f = float(self.total())
        if layer_weight > 0.0:
            f += layer_weight * (float(self.layers.sum()) / len(self.bins))
        return f


def _nfd_mutation(prob, ind: _Packing, rng, threshold, max_bins, extra_frac,
                  p_adm_w, p_adm_h) -> _Packing:
    """Algorithm 1 as a mutation: the bins that map worst (below
    ``threshold``, at most ``max_bins`` of them, ties broken by a draw) and
    a random ``extra_frac`` of the rest are emptied; their buffers, shuffled,
    are packed again by NFD and appended after the kept bins."""
    n = len(ind.bins)
    mask = np.zeros(n, dtype=bool)
    below = np.flatnonzero(ind.eff < threshold)
    if len(below) > max_bins:
        jitter = 1e-9 * rng.random(len(below))
        below = below[np.argsort(ind.eff[below] + jitter)][:max_bins]
    mask[below] = True
    if extra_frac > 0.0:
        mask |= rng.random(n) < extra_frac
    if not mask.any():
        mask[rng.integers(n)] = True
    pool = np.asarray([i for j in np.flatnonzero(mask) for i in ind.bins[j]],
                      dtype=np.int64)
    rng.shuffle(pool)
    new = _Packing.of(prob, _nfd_bins(prob, pool, rng, p_adm_w, p_adm_h))
    keep = ~mask
    return _Packing([ind.bins[j] for j in np.flatnonzero(keep)] + new.bins,
                    np.concatenate([ind.cost[keep], new.cost]),
                    np.concatenate([ind.eff[keep], new.eff]),
                    np.concatenate([ind.layers[keep], new.layers]))


def replay_ga_nfd(
    prob: ReferenceProblem,
    seed: int,
    n_pop: int,
    max_generations: int,
    n_tour: int = 5,
    p_mut: float = 0.4,
    p_adm_w: float = 0.0,
    p_adm_h: float = 0.1,
    nfd_threshold: float = 0.95,
    nfd_extra_frac: float = 0.01,
    nfd_max_bins: int = 12,
    layer_weight: float = 0.01,
) -> dict:
    """The GA-NFD answer for ``seed`` on a one-kind problem: ``{"cost",
    "bins", "kinds", "trace", "iterations"}``.

    A population of NFD packings (width-sorted for even ``k``); then, per
    generation, each individual mutated with probability ``p_mut``, the
    best tracked on the raw cost, and a tournament of ``n_tour`` drawn with
    replacement per slot on the fitness (cost plus ``layer_weight`` times
    the mean distinct layers per bin), the fittest kept in slot 0.  The
    trace is the best cost at the start, after each improvement, and once
    more at the end."""
    if prob.n_kinds != 1:
        raise ValueError("the GA-NFD replay covers one-kind problems only")
    rng = np.random.default_rng(seed)
    pop = [_Packing.of(prob, _nfd_solution(prob, rng, p_adm_w, p_adm_h, k % 2 == 0)[0])
           for k in range(n_pop)]
    costs = np.asarray([p.total() for p in pop], dtype=np.float64)
    fits = np.asarray([p.fitness(layer_weight) for p in pop])
    g = int(np.argmin(costs))
    best, best_cost = pop[g], int(costs[g])
    trace = [best_cost]
    for _ in range(int(max_generations)):
        for i in range(n_pop):
            if rng.random() < p_mut:
                pop[i] = _nfd_mutation(prob, pop[i], rng, nfd_threshold, nfd_max_bins,
                                       nfd_extra_frac, p_adm_w, p_adm_h)
                costs[i] = pop[i].total()
                fits[i] = pop[i].fitness(layer_weight)
        g = int(np.argmin(costs))
        if costs[g] < best_cost:
            best, best_cost = pop[g], int(costs[g])
            trace.append(best_cost)
        idx = rng.integers(n_pop, size=(n_pop, n_tour))
        winners = idx[np.arange(n_pop), np.argmin(fits[idx], axis=1)]
        winners[0] = int(np.argmin(fits))
        pop = [pop[int(w)] for w in winners]
        costs, fits = costs[winners], fits[winners]
    trace.append(best_cost)
    return dict(cost=best_cost, bins=[[int(i) for i in b] for b in best.bins],
                kinds=[0] * len(best.bins), trace=trace,
                iterations=int(max_generations))
