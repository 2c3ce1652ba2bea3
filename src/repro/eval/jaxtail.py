"""Jitted jax tails for the batched evaluators (DESIGN.md 7.2-7.4, 10).

For the mutation engine (``BatchedHWEvaluator``): one jitted function per
(mutated layer k, candidate-chunk size B) pair, closed over the static
network config.  Each computes, in int32:

    column update at k  ->  rank-1 update at k+1  ->  dense matmuls k+2..
    ->  unique-score max  ->  per-candidate correct counts

On the ``pallas`` backend the dense tail matmuls run through the bit-exact
``csd_matvec`` shift-add kernel (CSD digit planes are cached per layer and
invalidated on commit); otherwise they are plain int32 ``dot_general`` calls.
With a mesh, the whole tail is wrapped in ``shard_map`` over the validation
rows and the counts are ``psum``-reduced, so every device returns the global
count.

For the sweep engine (``QSweepEvaluator``): ``QSweepJax`` holds the device
mirrors of the validation rows and one jitted stacked forward per
(structure, activations, padded batch size) — a batched int32 ``dot_general``
per layer over the ``(Q, M, n)`` network stack, per-network array-q
requantization, and the same unique-score counts (DESIGN.md 10).  On the
``pallas`` backend the per-layer stacked matmul runs through the
``csd_qsweep`` digit-plane kernel instead — every network's weights expanded
to CSD planes at a shared depth, all q levels through the bit-exact
shift-add datapath in one dispatch (DESIGN.md 11.4); the jit key then also
carries the per-layer plane depths.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.intmlp import FRAC, act_requant

_NEG = -(1 << 30)


def _act_requant(acc, act: str, q: int):
    """The shared activation contract, on traced int32 jnp arrays."""
    return act_requant(acc, act, q, xp=jnp)


class JaxState:
    """Device mirrors of the evaluator's caches + the jitted tail registry."""

    def __init__(self, ev):
        self.ev = ev
        self._tails = {}
        self._planes: list = [None] * len(ev._mlp.weights)
        mesh = ev._mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._row = NamedSharding(mesh, P("data"))
            self._rep = NamedSharding(mesh, P())
        else:
            self._row = self._rep = None
        lab = ev._labels.astype(np.int32)
        self.lab = self._put_row(lab)
        self.lab_safe = self._put_row(np.maximum(lab, 0))
        self.W = [None] * len(ev._mlp.weights)
        self.bsh = [None] * len(ev._mlp.weights)
        self.sync(None)

    def _put_row(self, x):
        return jax.device_put(jnp.asarray(x), self._row)

    def _put_rep(self, x):
        return jax.device_put(jnp.asarray(x), self._rep)

    def sync(self, changed: Optional[dict]) -> None:
        """Refresh device mirrors after a commit.  ``changed`` (from the
        evaluator's commit) names the dirtied cache entries; None means a full
        rebuild (init / dense refresh)."""
        ev = self.ev
        n_layers = len(ev._mlp.weights)
        if changed is None:
            w_layers = range(n_layers)
            a_dirty = set(range(n_layers + 1))
            acc_dirty = set(range(n_layers))
            scores = True
            self.a = [None] * (n_layers + 1)
            self.acc = [None] * n_layers
        else:
            w_layers = [changed["layer"]]
            a_dirty, acc_dirty = changed["a"], changed["acc"]
            scores = changed["scores"]
        for l in w_layers:
            self.W[l] = self._put_rep(ev._mlp.weights[l].astype(np.int32))
            self.bsh[l] = self._put_rep(
                (ev._mlp.biases[l].astype(np.int64) << FRAC).astype(np.int32))
            self._planes[l] = None
        for l in a_dirty:
            self.a[l] = self._put_row(ev._a[l].astype(np.int32))
        for l in acc_dirty:
            self.acc[l] = self._put_row(ev._acc[l].astype(np.int32))
        if scores:
            self.maxexc = self._put_row(
                np.clip(ev._maxexc, _NEG, None).astype(np.int32))
            self.slab = self._put_row(ev._slab.astype(np.int32))

    def _need_planes(self, k: int) -> None:
        from repro.kernels import csd_expand
        for l in range(k + 2, len(self.ev._mlp.weights)):
            if self._planes[l] is None:
                self._planes[l] = self._put_rep(
                    jnp.asarray(csd_expand(self.ev._mlp.weights[l])))

    def counts(self, k: int, pad_to: int, wi, wj, dw, db,
               kind: str = "indep") -> np.ndarray:
        use_pallas = (self.ev.backend == "pallas"
                      and k + 2 < len(self.ev._mlp.weights))
        if use_pallas:
            self._need_planes(k)
        fn = self._tails.get((k, pad_to, kind))
        if fn is None:
            fn = self._build(k, pad_to, use_pallas, kind)
            self._tails[(k, pad_to, kind)] = fn
        planes = tuple(self._planes[l]
                       for l in range(k + 2, len(self.ev._mlp.weights))) \
            if use_pallas else ()
        out = fn(tuple(self.a), tuple(self.acc), tuple(self.W),
                 tuple(self.bsh), self.maxexc, self.slab, self.lab,
                 self.lab_safe, planes,
                 jnp.asarray(wi, jnp.int32), jnp.asarray(wj, jnp.int32),
                 jnp.asarray(dw, jnp.int32), jnp.asarray(db, jnp.int32))
        return np.asarray(out)

    def chain(self, k: int, pad_to: int, count0: int, wi, wj, dw, db):
        """Serial-chain scan over a candidate chunk: every accept/reject
        decision is made on-device against the evolving prefix state."""
        fn = self._tails.get((k, pad_to, "chain"))
        if fn is None:
            fn = self._build(k, pad_to, False, "chain")
            self._tails[(k, pad_to, "chain")] = fn
        counts, flags = fn(tuple(self.a), tuple(self.acc), tuple(self.W),
                           tuple(self.bsh), self.lab, self.lab_safe,
                           jnp.int32(count0),
                           jnp.asarray(wi, jnp.int32),
                           jnp.asarray(wj, jnp.int32),
                           jnp.asarray(dw, jnp.int32),
                           jnp.asarray(db, jnp.int32))
        return np.asarray(counts), np.asarray(flags)

    def tm_chain(self, k: int, pad_to: int, count0: int, dbsh: tuple,
                 wi, wj, dw0, dw1, has2, valid, pw0, pw1):
        """Device (lax.scan) variant of the time-multiplexed decision-tree
        chain (DESIGN.md 7.5): per step, the candidate pair is scored against
        the evolving prefix state, ranked by ``(count, value)`` descending,
        and on a failed pair the bias nudges run under ``lax.cond`` (so they
        cost nothing when the pair accepts) — first nudge clearing the
        running count wins, exactly like the host chain."""
        key = (k, pad_to, "tm", dbsh)
        fn = self._tails.get(key)
        if fn is None:
            fn = self._build_tm_chain(k, dbsh)
            self._tails[key] = fn
        outs = fn(tuple(self.a), tuple(self.acc), tuple(self.W),
                  tuple(self.bsh), self.lab, self.lab_safe,
                  jnp.int32(count0),
                  jnp.asarray(wi, jnp.int32), jnp.asarray(wj, jnp.int32),
                  jnp.asarray(dw0, jnp.int32), jnp.asarray(dw1, jnp.int32),
                  jnp.asarray(has2), jnp.asarray(valid),
                  jnp.asarray(pw0, jnp.int32), jnp.asarray(pw1, jnp.int32))
        return tuple(np.asarray(o) for o in outs)

    def _build_tm_chain(self, k: int, dbsh: tuple):
        ev = self.ev
        mlp = ev._mlp
        n_layers = len(mlp.weights)
        acts = tuple(mlp.activations)
        q = mlp.q
        n_out = mlp.weights[-1].shape[1]
        sharded = ev._mesh is not None
        last = k == n_layers - 1
        n_db = len(dbsh)

        def core(a, acc, w, bsh, lab, lab_safe, count0,
                 wi, wj, dw0, dw1, has2, valid, pw0, pw1):
            a_k = a[k]
            pen = n_out - 1 - jnp.arange(n_out, dtype=jnp.int32)

            def count_of(act_a):
                score = act_a * n_out + pen[None, :]
                smax = jnp.max(score, axis=1)
                slab = jnp.take_along_axis(score, lab_safe[:, None],
                                           axis=1)[:, 0]
                slab = jnp.where(lab < 0, _NEG, slab)
                cnt = jnp.sum(slab == smax, dtype=jnp.int32)
                return jax.lax.psum(cnt, "data") if sharded else cnt

            def step(carry, xs):
                wi_t, wj_t, dw0_t, dw1_t, has2_t, valid_t, pw0_t, pw1_t = xs
                if last:
                    acc_k, a_l, cnt = carry
                else:
                    acc_k, a_k1, acc_n, cnt = carry

                def cand_count(dw_t, dbsh_t):
                    buf = acc_k[:, wj_t] + a_k[:, wi_t] * dw_t + dbsh_t
                    h_new = _act_requant(buf, acts[k], q)
                    if last:
                        return count_of(a_l.at[:, wj_t].set(h_new))
                    dcol = h_new - a_k1[:, wj_t]
                    acc_cand = acc_n + dcol[:, None] * w[k + 1][wj_t][None, :]
                    act_a = _act_requant(acc_cand, acts[k + 1], q)
                    for l in range(k + 2, n_layers):
                        act_a = _act_requant(
                            jax.lax.dot_general(
                                act_a, w[l], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
                            + bsh[l][None, :], acts[l], q)
                    return count_of(act_a)

                # step 2b: the candidate pair, ranked by (count, value) desc
                c0 = cand_count(dw0_t, jnp.int32(0))
                c1 = jnp.where(has2_t, cand_count(dw1_t, jnp.int32(0)),
                               jnp.int32(-1))
                sel = (c1 > c0) | ((c1 == c0) & (pw1_t > pw0_t))
                cnt_best = jnp.where(sel, c1, c0)
                dw_best = jnp.where(sel, dw1_t, dw0_t)
                pair_ok = cnt_best >= cnt             # step 2c

                # step 2d: bias nudges only when the pair fails (lax.cond)
                def nudges(_):
                    cs = jnp.stack([cand_count(dw_best, jnp.int32(d))
                                    for d in dbsh]) if n_db else \
                        jnp.zeros(1, jnp.int32)
                    hit = cs >= cnt
                    idx = jnp.argmax(hit).astype(jnp.int32)
                    return hit.any(), idx, cs[idx]

                def no_nudges(_):
                    return jnp.bool_(False), jnp.int32(0), jnp.int32(0)

                db_ok, db_idx, cnt_db = jax.lax.cond(
                    valid_t & ~pair_ok, nudges, no_nudges, None)
                ok = valid_t & (pair_ok | db_ok)
                dbsh_fin = jnp.where(
                    pair_ok, jnp.int32(0),
                    jnp.asarray(dbsh, jnp.int32)[db_idx] if n_db
                    else jnp.int32(0))
                cnt_dec = jnp.where(pair_ok, cnt_best, cnt_db)

                # apply the chosen alternative's state update when accepted
                buf = acc_k[:, wj_t] + a_k[:, wi_t] * dw_best + dbsh_fin
                h_new = _act_requant(buf, acts[k], q)
                acc_k = jnp.where(ok, acc_k.at[:, wj_t].set(buf), acc_k)
                cnt = jnp.where(ok, cnt_dec, cnt)
                if last:
                    a_l = jnp.where(ok, a_l.at[:, wj_t].set(h_new), a_l)
                    carry = (acc_k, a_l, cnt)
                else:
                    dcol = h_new - a_k1[:, wj_t]
                    acc_nn = acc_n + dcol[:, None] * w[k + 1][wj_t][None, :]
                    a_k1 = jnp.where(ok, a_k1.at[:, wj_t].set(h_new), a_k1)
                    acc_n = jnp.where(ok, acc_nn, acc_n)
                    carry = (acc_k, a_k1, acc_n, cnt)
                return carry, (ok, sel, pair_ok, db_idx, cnt_best, cnt_dec)

            if last:
                carry0 = (acc[k], a[k + 1], count0)
            else:
                carry0 = (acc[k], a[k + 1], acc[k + 1], count0)
            _, outs = jax.lax.scan(step, carry0,
                                   (wi, wj, dw0, dw1, has2, valid, pw0, pw1))
            return outs

        if sharded:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            row, rep = P("data"), P()
            in_specs = (tuple([row] * len(ev._a)),
                        tuple([row] * len(ev._acc)),
                        tuple([rep] * n_layers), tuple([rep] * n_layers),
                        row, row, rep, rep, rep, rep, rep, rep, rep, rep, rep)
            core = shard_map(core, mesh=ev._mesh, in_specs=in_specs,
                             out_specs=(rep,) * 6, check_vma=False)
        return jax.jit(core)

    def _build_chain(self, k: int):
        ev = self.ev
        mlp = ev._mlp
        n_layers = len(mlp.weights)
        acts = tuple(mlp.activations)
        q = mlp.q
        n_out = mlp.weights[-1].shape[1]
        sharded = ev._mesh is not None
        last = k == n_layers - 1

        def core(a, acc, w, bsh, lab, lab_safe, count0, wi, wj, dw, db):
            a_k = a[k]
            pen = n_out - 1 - jnp.arange(n_out, dtype=jnp.int32)

            def count_of(act_a):
                """Correct count of one network's final activations."""
                score = act_a * n_out + pen[None, :]
                smax = jnp.max(score, axis=1)
                slab = jnp.take_along_axis(score, lab_safe[:, None],
                                           axis=1)[:, 0]
                slab = jnp.where(lab < 0, _NEG, slab)
                cnt = jnp.sum(slab == smax, dtype=jnp.int32)
                return jax.lax.psum(cnt, "data") if sharded else cnt

            def step(carry, xs):
                wi_t, wj_t, dw_t, db_t = xs
                if last:
                    acc_k, a_l, cnt = carry
                else:
                    acc_k, a_k1, acc_n, cnt = carry
                new_acc_col = (acc_k[:, wj_t] + a_k[:, wi_t] * dw_t + db_t)
                h_new = _act_requant(new_acc_col, acts[k], q)
                if last:
                    a_cand = a_l.at[:, wj_t].set(h_new)
                    cnt_c = count_of(a_cand)
                else:
                    dcol = h_new - a_k1[:, wj_t]
                    acc_cand = acc_n + dcol[:, None] * w[k + 1][wj_t][None, :]
                    act_a = _act_requant(acc_cand, acts[k + 1], q)
                    for l in range(k + 2, n_layers):
                        act_a = _act_requant(
                            jax.lax.dot_general(
                                act_a, w[l], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
                            + bsh[l][None, :], acts[l], q)
                    cnt_c = count_of(act_a)
                ok = cnt_c >= cnt
                acc_k = jnp.where(ok, acc_k.at[:, wj_t].set(new_acc_col),
                                  acc_k)
                cnt = jnp.where(ok, cnt_c, cnt)
                if last:
                    a_l = jnp.where(ok, a_cand, a_l)
                    carry = (acc_k, a_l, cnt)
                else:
                    a_k1 = jnp.where(ok, a_k1.at[:, wj_t].set(h_new), a_k1)
                    acc_n = jnp.where(ok, acc_cand, acc_n)
                    carry = (acc_k, a_k1, acc_n, cnt)
                return carry, (cnt_c, ok)

            if last:
                carry0 = (acc[k], a[k + 1], count0)
            else:
                carry0 = (acc[k], a[k + 1], acc[k + 1], count0)
            _, (counts, flags) = jax.lax.scan(step, carry0, (wi, wj, dw, db))
            return counts, flags

        if sharded:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            row, rep = P("data"), P()
            in_specs = (tuple([row] * len(ev._a)),
                        tuple([row] * len(ev._acc)),
                        tuple([rep] * n_layers), tuple([rep] * n_layers),
                        row, row, rep, rep, rep, rep, rep)
            core = shard_map(core, mesh=ev._mesh, in_specs=in_specs,
                             out_specs=(rep, rep), check_vma=False)
        return jax.jit(core)

    def _build(self, k: int, b_sz: int, use_pallas: bool,
               kind: str = "indep"):
        if kind == "chain":
            return self._build_chain(k)
        ev = self.ev
        mlp = ev._mlp
        n_layers = len(mlp.weights)
        acts = tuple(mlp.activations)
        q = mlp.q
        n_out = mlp.weights[-1].shape[1]
        sharded = ev._mesh is not None

        def dense_tail(act_a, w, bsh, planes):
            """Dense layers k+2.. over the (B, Mp, n) activations."""
            p_i = 0
            for l in range(k + 2, n_layers):
                x2 = act_a.reshape(-1, act_a.shape[2])
                if use_pallas:
                    from repro.kernels.ops import csd_matvec
                    y = csd_matvec(x2, planes=planes[p_i])
                    p_i += 1
                else:
                    y = jax.lax.dot_general(
                        x2, w[l], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                y = y + bsh[l][None, :]
                act_a = _act_requant(y, acts[l], q).reshape(
                    b_sz, -1, w[l].shape[1])
            return act_a

        def score_counts(act_a, lab, lab_safe):
            """Correct counts from final activations (B, Mp, n_out)."""
            pen = n_out - 1 - jnp.arange(n_out, dtype=jnp.int32)
            score = act_a * n_out + pen[None, None, :]
            smax = jnp.max(score, axis=2)                         # (B, Mp)
            slab_c = jnp.take_along_axis(
                score, lab_safe[None, :, None], axis=2)[..., 0]
            slab_c = jnp.where(lab[None, :] < 0, _NEG, slab_c)
            return jnp.sum(slab_c == smax, axis=1, dtype=jnp.int32)

        def spec_core(a, acc, w, bsh, maxexc, slab, lab, lab_safe, planes,
                      wi, wj, dw, db):
            """Prefix composition: entry c = candidates 0..c all applied."""
            deltas = a[k][:, wi] * dw[None, :] + db[None, :]      # (Mp, B)
            if k == n_layers - 1:
                onehot = (wj[:, None]
                          == jnp.arange(n_out, dtype=jnp.int32)[None, :])
                contrib = deltas.T[:, :, None] * onehot.astype(jnp.int32)[:, None, :]
                acc_p = acc[k][None] + jnp.cumsum(contrib, axis=0)
                act_a = _act_requant(acc_p, acts[k], q)
            else:
                iota = jnp.arange(b_sz, dtype=jnp.int32)
                pref = ((wj[None, :] == wj[:, None])
                        & (iota[None, :] <= iota[:, None])).astype(jnp.int32)
                cumdelta = jax.lax.dot_general(                   # (Mp, B):
                    deltas, pref, (((1,), (1,)), ((), ())),       # sum_{t<=c,
                    preferred_element_type=jnp.int32)             # same col}
                col_now = acc[k][:, wj] + cumdelta
                h_now = _act_requant(col_now, acts[k], q)
                h_prev = _act_requant(col_now - deltas, acts[k], q)
                dcol = h_now - h_prev                             # (Mp, B)
                w_rows = w[k + 1][wj]                             # (B, n_next)
                step = dcol.T[:, :, None] * w_rows[:, None, :]
                acc_p = acc[k + 1][None] + jnp.cumsum(step, axis=0)
                act_a = _act_requant(acc_p, acts[k + 1], q)
                act_a = dense_tail(act_a, w, bsh, planes)
            counts = score_counts(act_a, lab, lab_safe)
            if sharded:
                counts = jax.lax.psum(counts, "data")
            return counts

        def core(a, acc, w, bsh, maxexc, slab, lab, lab_safe, planes,
                 wi, wj, dw, db):
            acc_col = (acc[k][:, wj] + a[k][:, wi] * dw[None, :]
                       + db[None, :])                             # (Mp, B)
            new_col = _act_requant(acc_col, acts[k], q)
            if k == n_layers - 1:
                new_score = new_col * n_out + (n_out - 1 - wj)[None, :]
                smax = jnp.maximum(maxexc[:, wj], new_score)
                slab_c = jnp.where(lab[:, None] == wj[None, :],
                                   new_score, slab[:, None])
                counts = jnp.sum(slab_c == smax, axis=0, dtype=jnp.int32)
            else:
                dcol = new_col - a[k + 1][:, wj]                  # (Mp, B)
                w_rows = w[k + 1][wj]                             # (B, n_next)
                acc2 = (acc[k + 1][None, :, :]
                        + dcol.T[:, :, None] * w_rows[:, None, :])
                act_a = _act_requant(acc2, acts[k + 1], q)        # (B,Mp,n)
                act_a = dense_tail(act_a, w, bsh, planes)
                counts = score_counts(act_a, lab, lab_safe)
            if sharded:
                counts = jax.lax.psum(counts, "data")
            return counts

        core = spec_core if kind == "spec" else core
        if sharded:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            row, rep = P("data"), P()
            n_acc = len(ev._acc)
            in_specs = (tuple([row] * len(ev._a)), tuple([row] * n_acc),
                        tuple([rep] * n_layers), tuple([rep] * n_layers),
                        row, row, row, row,
                        tuple([rep] * (n_layers - k - 2)) if use_pallas
                        else (), rep, rep, rep, rep)
            core = shard_map(core, mesh=ev._mesh, in_specs=in_specs,
                             out_specs=rep, check_vma=False)
        return jax.jit(core)


class QSweepJax:
    """Device rows + the jitted stacked-forward registry for the multi-q
    sweep mode (DESIGN.md 10)."""

    def __init__(self, ev):
        self.ev = ev
        self._fns = {}
        mesh = ev._mesh
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._row = NamedSharding(mesh, P("data"))
            self._rep = NamedSharding(mesh, P())
        else:
            self._row = self._rep = None
        lab = ev._labels.astype(np.int32)
        self.x = jax.device_put(jnp.asarray(ev._x.astype(np.int32)),
                                self._row)
        self.lab = jax.device_put(jnp.asarray(lab), self._row)
        self.lab_safe = jax.device_put(jnp.asarray(np.maximum(lab, 0)),
                                       self._row)

    def qsweep_counts(self, mlps) -> np.ndarray:
        """Exact correct counts of the int32-safe networks in one jitted
        stacked forward.  Batches are padded (with copies of the first
        network) to a stable size so jit keys stay per-structure.  On the
        ``pallas`` backend the per-layer weight stacks ride as CSD digit
        planes (shared depth per layer) through ``csd_qsweep``."""
        n = len(mlps)
        qpad = 1 if n == 1 else max(n, self.ev.qchunk)
        padded = list(mlps) + [mlps[0]] * (qpad - n)
        n_layers = len(mlps[0].weights)
        # forward_int zips: surplus activation entries never run
        acts = tuple(mlps[0].activations[:n_layers])
        shapes = tuple(w.shape for w in mlps[0].weights)
        if self.ev.backend == "pallas":
            from repro.kernels import csd_expand_stack
            Ws_np = [csd_expand_stack([m.weights[l] for m in padded])
                     for l in range(n_layers)]
            depths = tuple(p.shape[1] for p in Ws_np)
            key = (shapes, acts, qpad, "pallas", depths)
        else:
            Ws_np = [np.stack([np.asarray(m.weights[l], np.int64)
                               for m in padded]).astype(np.int32)
                     for l in range(n_layers)]
            depths = None
            key = (shapes, acts, qpad)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build_qsweep(acts, qpad, pallas=depths is not None)
            self._fns[key] = fn
        Ws = tuple(jax.device_put(jnp.asarray(w), self._rep) for w in Ws_np)
        bshs = tuple(jax.device_put(jnp.asarray((np.stack(
            [np.asarray(m.biases[l], np.int64) for m in padded]
        ) << FRAC).astype(np.int32)), self._rep) for l in range(n_layers))
        qs = jnp.asarray([m.q for m in padded], jnp.int32)
        out = fn(self.x, self.lab, self.lab_safe, qs, Ws, bshs)
        return np.asarray(out)[:n].astype(np.int64)

    def _build_qsweep(self, acts, qpad: int, pallas: bool = False):
        ev = self.ev
        n_layers = len(acts)
        q_dims = (((2,), (1,)), ((0,), (0,)))   # (Q,M,i) @ (Q,i,o) -> (Q,M,o)
        sharded = ev._mesh is not None

        def core(x, lab, lab_safe, qs, Ws, bshs):
            n_out = Ws[-1].shape[-1]
            a = jnp.broadcast_to(x[None], (qpad,) + x.shape)
            qcol = qs[:, None, None]
            for l in range(n_layers):
                if pallas:          # stacked shift-add datapath (11.4)
                    from repro.kernels.ops import csd_qsweep
                    acc = csd_qsweep(a, Ws[l])
                else:
                    acc = jax.lax.dot_general(
                        a, Ws[l], q_dims, preferred_element_type=jnp.int32)
                acc = acc + bshs[l][:, None, :]
                a = _act_requant(acc, acts[l], qcol)
            pen = n_out - 1 - jnp.arange(n_out, dtype=jnp.int32)
            score = a * n_out + pen[None, None, :]
            smax = jnp.max(score, axis=2)
            slab = jnp.take_along_axis(
                score, lab_safe[None, :, None], axis=2)[..., 0]
            slab = jnp.where(lab[None, :] < 0, _NEG, slab)
            counts = jnp.sum(slab == smax, axis=1, dtype=jnp.int32)
            if sharded:
                counts = jax.lax.psum(counts, "data")
            return counts

        if sharded:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            row, rep = P("data"), P()
            in_specs = (row, row, row, rep, tuple([rep] * n_layers),
                        tuple([rep] * n_layers))
            core = shard_map(core, mesh=ev._mesh, in_specs=in_specs,
                             out_specs=rep, check_vma=False)
        return jax.jit(core)
