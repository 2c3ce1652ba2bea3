"""Paged-slot serving engine: chunked prefill, admission queue, slot reuse.

The production engine (DESIGN.md 13).  ``ServeEngine`` replaces the seed's
"continuous-batching-lite" loop (kept verbatim below as
:class:`ReferenceEngine`, the parity oracle) with:

* a slot-based paged KV cache (:class:`repro.runtime.kvcache.PagedKVCache`):
  fixed ``max_batch`` x ``max_context`` capacity, per-slot position
  counters, slot reuse the moment a request finishes — no whole-batch
  ``_pad_kv`` re-padding; with ``kv_block_size > 0`` the cache is BLOCK
  PAGED (fixed-size blocks + per-slot block tables, DESIGN.md 15) and both
  dispatches route attention through the block-table gather;
* decoupled prefill / decode dispatches with BATCHED CHUNKED prefill: up to
  ``prefill_batch`` chunks from DIFFERENT prefilling slots are ingested per
  engine step in one fixed-shape (P, chunk) dispatch, so a long prompt
  never stalls the resident decode batch, the oldest prompt never
  head-of-line-blocks the rest, and finished slots refill mid-stream;
* a request queue with admission control (reject/truncate prompts beyond
  ``max_context``, per-request queue deadlines, FIFO by arrival) and
  per-request latency stats (queue_s, prefill_s, first_token_s, decode
  tokens/s);
* a vectorized counted-PRNG sampler: one jitted Gumbel-argmax draw keyed on
  (seed, rid, token index), so sampled streams are reproducible across runs
  AND across batch compositions;
* optional ``shard_map`` data parallelism over the decode step (slots
  sharded across mesh devices, params replicated — the eval-layer idiom)
  OR tensor parallelism (``tensor_parallel=True``: heads / FFN columns
  sharded, outputs psum-combined, DESIGN.md 16.3) — tensor parallelism
  composes with block paging (the pool's head dim shards; the block-id
  namespace stays global), so ``data_parallel + kv_block_size`` routes
  there instead of raising;
* a ``decode_kernel`` selector for the block-paged attention read:
  ``"dense"`` (gather + masked full-row pass, the default oracle),
  ``"reference"`` (lax.scan block-online-softmax straight off the pool),
  ``"fused"`` (the Pallas fused kernel, DESIGN.md 16 — bytes read scale
  with actual per-slot lengths);
* in-place cache updates: both jitted dispatches DONATE the KV-cache
  pytree (``donate_argnums``), so a decode step updates the pool's buffers
  instead of allocating a second full-size copy.

With ``quantized=True`` the matmul weights serve as int8-PoT (repro.quant);
dequantization happens INSIDE the jitted dispatches so the resident bytes
really are the quantized ones — the paper's thesis at serving scale.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.nn.model import Model
from repro.nn.types import ArchConfig
from repro.quant import serving_ledger, serving_quant
from repro.runtime import kvcache
from repro.runtime.kvcache import ADMIT_REJECT, ADMIT_TRUNCATE, PagedKVCache

__all__ = ["ServeEngine", "ReferenceEngine", "Request", "summarize"]


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    deadline_s: float | None = None   # max queue wait before expiry
    # streaming callback: on_token(rid, step, token) fires the moment each
    # generated token lands (step = 0-based index into the final
    # ``out_tokens``), in both ServeEngine and ReferenceEngine
    on_token: object = None
    out_tokens: list = field(default_factory=list)
    done: bool = False
    # lifecycle: new -> queued -> running -> done | rejected | expired
    status: str = "new"
    truncated: bool = False
    arrival_s: float = 0.0
    stats: dict = field(default_factory=dict)


def summarize(requests, engine=None) -> dict:
    """p50/p99 latency + throughput over a served request list.

    Reads the per-request ``stats`` the paged engine fills in: total_s
    (arrival -> done), first_token_s (arrival -> first sampled token), and
    decode_tokens/decode_s.  Rejected/expired requests count in their own
    buckets and are excluded from the percentiles.

    ``engine``: the engine that served the requests.  Its aggregate
    ``stats["decode_s"]`` is the true batched-decode wall time, which is the
    only honest denominator for ``decode_tok_s`` — each request's own
    ``decode_s`` counts the FULL wall time of every shared dispatch it rode
    in, so no combination of the per-request values recovers the aggregate.
    Without an engine ``decode_tok_s`` is reported as 0.0; read the
    per-request ``stats["decode_tok_s"]`` instead.
    """
    done = [r for r in requests if r.status == "done"]

    def pct(key, p):
        xs = sorted(r.stats[key] for r in done if key in r.stats)
        if not xs:
            return 0.0
        return xs[min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))]

    dec_tok = sum(r.stats.get("decode_tokens", 0) for r in done)
    dec_s = engine.stats.get("decode_s", 0.0) if engine is not None else 0.0
    return {
        "n": len(requests), "done": len(done),
        "rejected": sum(r.status == "rejected" for r in requests),
        "expired": sum(r.status == "expired" for r in requests),
        "truncated": sum(r.truncated for r in requests),
        "p50_total_s": pct("total_s", 50), "p99_total_s": pct("total_s", 99),
        "p50_first_token_s": pct("first_token_s", 50),
        "p99_first_token_s": pct("first_token_s", 99),
        "decode_tokens": dec_tok,
        "decode_tok_s": dec_tok / dec_s if dec_s > 0 else 0.0,
    }


@dataclass
class _Slot:
    """Host-side state of one cache slot while a request runs in it."""
    req: Request
    n_prefilled: int = 0          # prompt tokens already ingested
    phase: str = "prefill"        # prefill -> decode
    assigned_s: float = 0.0
    seq: int = 0                  # assignment sequence (prefill FIFO order)


#: decoder-layer leaves whose LAST dim is a head/FFN-column output
#: (sharded over the tensor-parallel axis) and whose dim -2 is the sharded
#: CONTRACTION dim of a row-parallel matmul (output is a psum-ed partial).
_TP_COL = frozenset({"wq", "wk", "wv", "bq", "bk", "bv", "wg", "wu"})
_TP_ROW = frozenset({"wo", "wd"})


def _tp_param_specs(params, axis):
    """Per-path PartitionSpecs for tensor-parallel decode.

    Inside the stacked ``layers`` pytree: q/k/v projections, their biases,
    and the FFN up/gate matrices shard their last (output-column) dim;
    ``wo``/``wd`` shard dim -2 (the contraction dim — their outputs are
    partial sums that ``Model._tp_reduce`` psums).  The name-based rule
    covers every nesting level (attn, mlp, moe experts, moe shared/dense
    residual MLPs); routers, norms, embeddings, and the LM head replicate.
    """
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        if "layers" not in keys:
            return P()
        name = keys[-1]
        if name in _TP_COL:
            return P(*([None] * (leaf.ndim - 1)), axis)
        if name in _TP_ROW:
            return P(*([None] * (leaf.ndim - 2)), axis, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


class ServeEngine:
    """Slot-paged serving engine for the standard-KV families (dense/moe)."""

    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 max_context: int = 512, eos_id: int = 0,
                 quantized: bool = False, quant_bits=8,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_chunk: int = 64, prefill_batch: int = 1,
                 kv_block_size: int = 0, kv_gather: str = "take",
                 decode_kernel: str = "dense", admission: str = "reject",
                 data_parallel: bool = False, tensor_parallel: bool = False,
                 mesh=None, clock=time.monotonic):
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"paged serving supports standard-KV families, not "
                f"{cfg.family!r} — use ReferenceEngine")
        if kv_gather not in ("take", "pallas"):
            raise ValueError(f"unknown kv_gather {kv_gather!r}")
        if decode_kernel == "auto":
            # measured dispatch (DESIGN.md 17): the cached race winner for
            # this (platform, batch x context x block) neighbourhood, else
            # the static "dense" rule.  Consult-only — the autotune bench
            # lane does the measuring; both kernels are bit-identical
            # (DESIGN.md 16), so the pick only moves wall-clock.  Without a
            # block pool only the gather+dense route exists at all.
            if kv_block_size:
                from repro import tune
                decode_kernel = tune.decide(
                    "decode_kernel",
                    shape=(max_batch, max_context, kv_block_size),
                    dtype=str(cfg.dtype), candidates=("dense", "fused"),
                    heuristic="dense")
            else:
                decode_kernel = "dense"
        if decode_kernel not in ("dense", "reference", "fused"):
            raise ValueError(f"unknown decode_kernel {decode_kernel!r}")
        if decode_kernel != "dense" and not kv_block_size:
            raise ValueError(
                "decode_kernel='reference'/'fused' read the block pool "
                "directly; they need kv_block_size > 0")
        if data_parallel and tensor_parallel:
            raise ValueError(
                "pick ONE of data_parallel / tensor_parallel decode")
        if tensor_parallel and quantized:
            raise NotImplementedError(
                "tensor-parallel decode serves float params (sharding the "
                "per-channel PoT qtree is not wired)")
        if data_parallel and kv_block_size:
            # slot-sharded (data-parallel) decode cannot compose with the
            # block pool: a per-shard slot row indexes the GLOBAL block-id
            # namespace.  The sharded route that does compose shards HEADS
            # (the pool's Hkv dim is layout-local), so route there.
            data_parallel, tensor_parallel = False, True
        self.cfg = cfg
        self.model = Model(cfg)
        self.max_batch = max_batch
        self.max_context = max_context
        self.eos_id = eos_id
        self.temperature = temperature
        self.admission = admission
        self.prefill_chunk = min(prefill_chunk, max_context)
        self.prefill_batch = max(1, min(prefill_batch, max_batch))
        self.kv_block_size = kv_block_size
        self.kv_gather = kv_gather
        self.decode_kernel = decode_kernel
        self.tensor_parallel = tensor_parallel
        self.clock = clock
        self._key = jax.random.PRNGKey(seed)
        dt = jnp.dtype(cfg.dtype)
        if quantized:
            # weights live in HBM as int8 + PoT exponents; dequantization
            # happens INSIDE the jitted steps (exact: PoT scales), so the
            # resident bytes really are the quantized ones (cf. quant_bytes).
            # quant_bits is a global rung (int) OR a {path: bits} Mapping —
            # a mixed_bitwidth_search assignment serves with no extra code,
            # since every qleaf carries its own scheme through dequant.
            self.quant_tree, deq, self.quant_bytes = serving_quant(
                params, bits=quant_bits, dtype=dt)
            self.params = self.quant_tree
            self.serving_sheet = serving_ledger(
                params, bits=quant_bits, act_itemsize=float(dt.itemsize))
        else:
            self.params = params
            self.quant_tree = None
            self.quant_bytes = None
            self.serving_sheet = None
            deq = lambda t: t                                   # noqa: E731
        self.cache = PagedKVCache(self.model, max_batch, max_context,
                                  block_size=kv_block_size)
        # analytic decode-attention KV traffic: bytes one logical cache row
        # (K + V, every layer) occupies — priced per dispatch by
        # _decode_kv_bytes into stats["kv_bytes_read"]
        itemsize = jax.tree.leaves(self.cache.data)[0].dtype.itemsize
        self._kv_row_bytes = (cfg.n_layers * cfg.n_kv_heads
                              * cfg.head_dim_ * 2 * itemsize)
        self._decode = self._build_decode(deq, data_parallel,
                                          tensor_parallel, mesh)
        # donate_argnums=(1,): the cache pytree is consumed by every
        # dispatch and rebound to the returned one (self.cache.data = ...),
        # so XLA updates the KV buffers in place instead of holding the old
        # and new pool live at once
        if kv_block_size:
            self._prefill = jax.jit(
                lambda pt, cache, tok, slots, offs, nv, tbl:
                self.model.prefill_chunks(deq(pt), cache, tok, slots, offs,
                                          nv, block_table=tbl,
                                          kv_gather=kv_gather),
                donate_argnums=(1,))
        else:
            self._prefill = jax.jit(
                lambda pt, cache, tok, slots, offs, nv:
                self.model.prefill_chunks(deq(pt), cache, tok, slots, offs,
                                          nv),
                donate_argnums=(1,))
        self._draw = jax.jit(jax.vmap(self._draw_one))
        self.queue: deque = deque()        # FIFO admitted requests
        self.slots: dict = {}              # slot id -> _Slot
        self.events: list = []             # (step, action, rid, slot)
        self._step_idx = 0
        self._seq = 0
        self.stats = {"prefill_tokens": 0, "decode_tokens": 0,
                      "prefill_s": 0.0, "decode_s": 0.0,
                      "prefill_chunks": 0, "prefill_dispatches": 0,
                      "decode_steps": 0, "steps": 0,
                      "admitted": 0, "rejected": 0, "truncated": 0,
                      "expired": 0, "finished": 0, "kv_bytes_read": 0.0}

    # ------------------------------------------------------------ dispatches
    def _build_decode(self, deq, data_parallel: bool, tensor_parallel: bool,
                      mesh):
        if tensor_parallel:
            return self._build_tp_decode(deq, mesh)
        if self.kv_block_size:
            return jax.jit(
                lambda pt, cache, tok, pos, tbl: self.model.decode_step(
                    deq(pt), cache, tok, pos, block_table=tbl,
                    kv_gather=self.kv_gather,
                    decode_kernel=self.decode_kernel),
                donate_argnums=(1,))

        def step(pt, cache, tok, pos):
            return self.model.decode_step(deq(pt), cache, tok, pos)

        if not data_parallel:
            return jax.jit(step, donate_argnums=(1,))
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), ("data",))
        ndev = mesh.devices.size
        if self.max_batch % ndev:
            raise ValueError(f"max_batch={self.max_batch} must divide over "
                             f"{ndev} devices for data-parallel decode")
        # eval-layer idiom (DESIGN.md 7.4): shard the batch-like dim, keep
        # params replicated; the decode step is row-independent so no
        # collective is needed — out_specs reassemble logits and cache.
        row = jax.tree.map(
            lambda l: P(None, "data", *([None] * (l.ndim - 2))),
            self.cache.data)
        rep = jax.tree.map(lambda _: P(), self.params)
        fn = shard_map(step, mesh=mesh,
                       in_specs=(rep, row, P("data", None), P("data")),
                       out_specs=(P("data", None, None), row),
                       check_vma=False)
        return jax.jit(fn, donate_argnums=(1,))

    def _build_tp_decode(self, deq, mesh):
        """Tensor-parallel decode (DESIGN.md 16.3): heads and FFN columns
        shard over the mesh axis; each device runs the full decode step on
        a HEAD/COLUMN-LOCAL model (a cfg with n_heads / n_kv_heads / d_ff
        divided by the device count and head_dim pinned — head_dim_ is
        otherwise derived from d_model // n_heads) and ``Model._tp_reduce``
        psums the attention / FFN partial sums back to the full residual.

        The KV cache shards on its Hkv dim — dim 3 of BOTH the contiguous
        (L, n_slots, C, Hkv, hd) and the block-paged (L, NB, bs, Hkv, hd)
        layouts — which is why tensor parallelism composes with block
        paging: block ids stay a global (replicated) namespace, only the
        head content splits.  Tokens / positions / block table replicate;
        logits come out replicated (every device holds the psum result).

        psum re-associates the wo / wd contraction, so logits match the
        single-device route to float tolerance, not bitwise — TOKEN parity
        is what the subprocess test asserts.
        """
        import dataclasses as _dc
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        cfg = self.cfg
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), ("model",))
        ndev = mesh.devices.size
        axis = mesh.axis_names[0]
        for name in ("n_heads", "n_kv_heads", "d_ff"):
            if getattr(cfg, name) % ndev:
                raise ValueError(
                    f"tensor-parallel decode needs {name}="
                    f"{getattr(cfg, name)} divisible by {ndev} devices")
        if cfg.dense_ff and cfg.dense_ff % ndev:
            raise ValueError(
                f"tensor-parallel decode needs dense_ff={cfg.dense_ff} "
                f"divisible by {ndev} devices")
        local_cfg = _dc.replace(
            cfg, head_dim=cfg.head_dim_,
            n_heads=cfg.n_heads // ndev,
            n_kv_heads=cfg.n_kv_heads // ndev,
            d_ff=cfg.d_ff // ndev,
            dense_ff=cfg.dense_ff // ndev if cfg.dense_ff else 0)
        local = Model(local_cfg)
        local.tp_axis = axis

        def step(pt, cache, tok, pos, *tbl):
            return local.decode_step(
                deq(pt), cache, tok, pos,
                block_table=tbl[0] if tbl else None,
                kv_gather=self.kv_gather, decode_kernel=self.decode_kernel)

        pspec = _tp_param_specs(self.params, axis)
        head = jax.tree.map(lambda l: P(None, None, None, axis, None),
                            self.cache.data)
        in_specs = (pspec, head, P(), P())
        if self.kv_block_size:
            in_specs += (P(),)
        fn = shard_map(step, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(), head), check_vma=False)
        return jax.jit(fn, donate_argnums=(1,))

    def _draw_one(self, rid, step, logits):
        """Counted-PRNG temperature sample: key = f(seed, rid, token idx).

        One Gumbel-argmax per row, vmapped into a single vectorized draw —
        the stream each request sees depends only on (seed, rid, step),
        never on which other requests share the batch.
        """
        k = jax.random.fold_in(jax.random.fold_in(self._key, rid), step)
        g = jax.random.gumbel(k, logits.shape)
        return jnp.argmax(logits / self.temperature + g)

    def _sample(self, logits: np.ndarray, rids, steps) -> np.ndarray:
        """logits: (B, V) f32; rids/steps: per-row (B,) int arrays."""
        if self.temperature <= 0:
            return np.argmax(logits, axis=-1)
        return np.asarray(self._draw(jnp.asarray(rids, jnp.uint32),
                                     jnp.asarray(steps, jnp.uint32),
                                     jnp.asarray(logits)))

    # ------------------------------------------------------------- frontend
    def _now(self, now):
        return self.clock() if now is None else now

    def submit(self, req: Request, now=None) -> str:
        """Admission: reject/truncate over-long prompts, then enqueue FIFO."""
        now = self._now(now)
        verdict, eff = kvcache.admit(len(req.prompt), self.max_context,
                                     self.admission)
        if verdict == ADMIT_REJECT:
            req.status = "rejected"
            req.done = True
            self.stats["rejected"] += 1
            self.events.append((self._step_idx, "reject", req.rid, None))
            return req.status
        if verdict == ADMIT_TRUNCATE:
            req.prompt = np.asarray(req.prompt)[-eff:]   # keep the tail
            req.truncated = True
            self.stats["truncated"] += 1
            self.events.append((self._step_idx, "truncate", req.rid, None))
        # decode writes reach position len(prompt) + max_new - 2; cap so the
        # slot never wraps (the seed engine's overflow, fixed at admission)
        req.stats["max_new_eff"] = min(
            req.max_new_tokens, self.max_context + 1 - len(req.prompt))
        req.status = "queued"
        req.arrival_s = now
        self.stats["admitted"] += 1
        self.queue.append(req)
        self.events.append((self._step_idx, "admit", req.rid, None))
        return req.status

    # ------------------------------------------------------------ main loop
    def step(self, now=None) -> list:
        """One scheduling iteration: expire -> refill slots -> one batched
        prefill dispatch (up to ``prefill_batch`` chunks) -> one decode step
        over every decoding slot.  Returns requests
        finished this step.  ``now`` injects the caller's timebase: every
        timestamp this step records (expiry, queue_s, first_token_s,
        total_s) then comes from it, never from ``self.clock``."""
        t = self._now(now)
        self._step_idx += 1
        self.stats["steps"] += 1
        self._expire(t)
        self._assign(t)
        # sub-steps get the RAW argument: with now=None they re-read the
        # clock after their dispatch (t_first/t_done include dispatch wall
        # time); with an injected now they stay in the caller's timebase
        self._prefill_step(now)
        return self._decode_step(now)

    def run(self, requests: list) -> list:
        """Serve a list of Requests to completion; returns them filled."""
        for r in requests:
            self.submit(r)
        while self.queue or self.slots:
            self.step()
        return requests

    def _expire(self, now):
        meta = [(r.rid, r.arrival_s,
                 None if r.deadline_s is None else r.arrival_s + r.deadline_s)
                for r in self.queue]
        expired, _ = kvcache.expire(meta, now)
        if not expired:
            return
        dead = set(expired)
        for r in list(self.queue):
            if r.rid in dead:
                self.queue.remove(r)
                r.status = "expired"
                r.done = True
                r.stats["queue_s"] = now - r.arrival_s
                self.stats["expired"] += 1
                self.events.append((self._step_idx, "expire", r.rid, None))

    def _assign(self, now):
        while self.queue and self.cache.n_free:
            r = self.queue.popleft()
            slot = self.cache.alloc(r.rid)
            r.status = "running"
            r.stats["queue_s"] = now - r.arrival_s
            self.slots[slot] = _Slot(req=r, assigned_s=now, seq=self._seq)
            self._seq += 1
            self.events.append((self._step_idx, "assign", r.rid, slot))

    def _emit(self, r):
        """Fire the streaming callback for the token just appended."""
        if r.on_token is not None:
            r.on_token(r.rid, len(r.out_tokens) - 1, r.out_tokens[-1])

    def _prefill_step(self, now):
        """Ingest up to ``prefill_batch`` chunks from DIFFERENT prefilling
        slots in ONE fixed-shape (P, chunk) dispatch, oldest assignment
        first.  Unused rows ride along exactly like the decode dispatch's
        dummy rows: offset = max_context puts every one of their scatter
        writes out of range (``mode="drop"``) and their logits are ignored.
        The scatter semantics also retire the old final-chunk host-side
        shrink — an out-of-range position simply vanishes instead of
        clamping, so ONE (P, chunk) shape compiles, ever."""
        pending = sorted((st.seq, slot) for slot, st in self.slots.items()
                         if st.phase == "prefill")
        if not pending:
            return
        picked = [slot for _, slot in pending[:self.prefill_batch]]
        P, chunk = self.prefill_batch, self.prefill_chunk
        toks = np.zeros((P, chunk), np.int32)
        slots = np.zeros(P, np.int32)
        offs = np.full(P, self.max_context, np.int32)   # dummies: all-drop
        nval = np.ones(P, np.int32)
        ns = []
        for i, slot in enumerate(picked):
            st = self.slots[slot]
            r = st.req
            n = min(chunk, len(r.prompt) - st.n_prefilled)
            toks[i, :n] = r.prompt[st.n_prefilled:st.n_prefilled + n]
            slots[i], offs[i], nval[i] = slot, st.n_prefilled, n
            ns.append(n)
            if self.kv_block_size:
                self.cache.ensure(slot, st.n_prefilled + n)
        t0 = time.time()
        args = (self.params, self.cache.data, jnp.asarray(toks),
                jnp.asarray(slots), jnp.asarray(offs), jnp.asarray(nval))
        if self.kv_block_size:
            args += (jnp.asarray(self.cache.block_table),)
        logits, self.cache.data = self._prefill(*args)
        logits = np.asarray(logits)
        dt = time.time() - t0
        self.stats["prefill_s"] += dt
        self.stats["prefill_tokens"] += int(sum(ns))
        self.stats["prefill_chunks"] += len(picked)
        self.stats["prefill_dispatches"] += 1
        done_rows = []
        for i, slot in enumerate(picked):
            st = self.slots[slot]
            st.req.stats["prefill_s"] = \
                st.req.stats.get("prefill_s", 0.0) + dt
            st.n_prefilled += ns[i]
            self.cache.lengths[slot] = st.n_prefilled
            if st.n_prefilled >= len(st.req.prompt):
                done_rows.append((i, slot))
        if not done_rows:
            return
        # prompts fully ingested: sample their first tokens from the rows'
        # last-valid-position logits (token index 0; EOS is deliberately NOT
        # checked here — the reference engine ignores a first-token EOS and
        # parity pins that behavior)
        rows = np.array([i for i, _ in done_rows])
        rids = np.array([self.slots[s].req.rid for _, s in done_rows])
        nxt = self._sample(logits[rows], rids, np.zeros(len(rows), np.int64))
        t_first = self._now(now)
        for j, (i, slot) in enumerate(done_rows):
            st = self.slots[slot]
            r = st.req
            r.out_tokens.append(int(nxt[j]))
            self._emit(r)
            r.stats["first_token_s"] = t_first - r.arrival_s
            st.phase = "decode"
            if len(r.out_tokens) >= r.stats["max_new_eff"]:
                self._finish(slot, t_first)

    def _decode_kv_bytes(self, pos) -> float:
        """Analytic KV bytes one decode dispatch reads for its attention,
        summed over every slot row in the fixed-shape batch (idle rows ride
        along and their cache IS read).  Host-side pricing, not a
        measurement — but it is exact for each route's access pattern:

        * contiguous slab — the dense masked pass streams every slot's full
          ``max_context`` row once;
        * block pool, ``decode_kernel="dense"`` — gather reads the whole
          table's blocks, writes the contiguous copy, and the dense pass
          reads it back: 3x full-row traffic;
        * ``"reference"`` — one pass over every table entry (the scan takes
          all ``nb`` blocks, masked or not);
        * ``"fused"`` — one pass over just ``ceil(len/bs)`` blocks per slot
          (the effective-table remap collapses the masked tail into a
          revisit), so bytes scale with the ACTUAL per-slot lengths.
        """
        C = self.max_context
        clen = np.minimum(np.asarray(pos) + 1, C)
        if not self.kv_block_size:
            rows = C * clen.size
        elif self.decode_kernel == "dense":
            rows = 3 * C * clen.size
        elif self.decode_kernel == "reference":
            rows = C * clen.size
        else:                                  # fused
            bs = self.kv_block_size
            rows = int(np.sum(-(-clen // bs) * bs))
        return float(rows) * self._kv_row_bytes

    def decode_inputs(self):
        """The next decode dispatch's inputs: ``(active, rids, steps, args)``
        with ``args`` = (params, cache, tokens, positions[, block table]) as
        the jitted decode step takes them, or None when no slot decodes.
        Reserves the blocks the fed tokens' KV lands in."""
        active = [slot for slot, st in self.slots.items()
                  if st.phase == "decode"]
        if not active:
            return None
        B = self.max_batch
        toks = np.zeros((B, 1), np.int32)
        pos = np.minimum(self.cache.lengths.copy(), self.max_context - 1)
        rids = np.zeros(B, np.int64)
        steps = np.zeros(B, np.int64)
        for slot in active:
            r = self.slots[slot].req
            toks[slot, 0] = r.out_tokens[-1]
            pos[slot] = self.cache.lengths[slot]
            rids[slot] = r.rid
            steps[slot] = len(r.out_tokens)
            if self.kv_block_size:
                # the fed token's KV lands at position lengths[slot]
                self.cache.ensure(slot, int(self.cache.lengths[slot]) + 1)
        args = (self.params, self.cache.data, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32))
        if self.kv_block_size:
            args += (jnp.asarray(self.cache.block_table),)
        return active, rids, steps, args

    def _decode_step(self, now):
        """One decode token for EVERY decoding slot in a single fixed-shape
        dispatch.  Idle/prefilling slots ride along as dummy rows: their
        write position is their own next-write index, so the garbage they
        deposit is always overwritten before the slot length reaches it."""
        inputs = self.decode_inputs()
        if inputs is None:
            return []
        active, rids, steps, args = inputs
        # host copy of the dispatched positions (active slots sit below
        # max_context - 1 by the admission cap, so the clamp is theirs too)
        pos = np.minimum(self.cache.lengths, self.max_context - 1)
        t0 = time.time()
        lg, self.cache.data = self._decode(*args)
        lg = np.asarray(lg)[:, 0]
        dt = time.time() - t0
        self.stats["decode_s"] += dt
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += len(active)
        self.stats["kv_bytes_read"] += self._decode_kv_bytes(pos)
        nxt = self._sample(lg, rids, steps)
        t_done = self._now(now)
        finished = []
        for slot in active:
            st = self.slots[slot]
            r = st.req
            self.cache.lengths[slot] += 1     # the fed token's KV was written
            tok = int(nxt[slot])
            r.out_tokens.append(tok)
            self._emit(r)
            r.stats["decode_tokens"] = r.stats.get("decode_tokens", 0) + 1
            r.stats["decode_s"] = r.stats.get("decode_s", 0.0) + dt
            if tok == self.eos_id or \
                    len(r.out_tokens) >= r.stats["max_new_eff"]:
                finished.append(r)
                self._finish(slot, t_done)
        return finished

    def _finish(self, slot, now):
        st = self.slots.pop(slot)
        r = st.req
        r.done = True
        r.status = "done"
        r.stats["total_s"] = now - r.arrival_s
        dec_s = r.stats.get("decode_s", 0.0)
        r.stats["decode_tok_s"] = (r.stats.get("decode_tokens", 0) / dec_s
                                   if dec_s > 0 else 0.0)
        self.cache.release(slot)
        self.stats["finished"] += 1
        self.events.append((self._step_idx, "release", r.rid, slot))


class ReferenceEngine:
    """The seed's continuous-batching-lite engine, retained as the parity
    oracle: fixed decode batch, whole-batch left-padded prefill, `_pad_kv`
    re-padding, batch refresh only at prefill boundaries.  Handles every
    model family (the paged engine covers dense/moe).  The admission
    overflow is fixed here too — prompts beyond ``max_context`` are rejected
    or tail-truncated at enqueue instead of corrupting the cache."""

    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 max_context: int = 512, eos_id: int = 0,
                 quantized: bool = False, quant_bits=8,
                 temperature: float = 0.0,
                 seed: int = 0, admission: str = "reject"):
        self.cfg = cfg
        self.model = Model(cfg)
        self.max_batch = max_batch
        self.max_context = max_context
        self.eos_id = eos_id
        self.temperature = temperature
        self.admission = admission
        self.rng = np.random.default_rng(seed)
        self.serving_sheet = None
        if quantized:
            dt = jnp.dtype(cfg.dtype)
            self.quant_tree, deq, _ = serving_quant(
                params, bits=quant_bits, dtype=dt)
            self.serving_sheet = serving_ledger(
                params, bits=quant_bits, act_itemsize=float(dt.itemsize))
            self.params = self.quant_tree
            self._decode = jax.jit(
                lambda qt, cache, tok, pos: self.model.decode_step(
                    deq(qt), cache, tok, pos))
            self._prefill = jax.jit(
                lambda qt, batch: self.model.prefill(deq(qt), batch))
        else:
            self.params = params
            self.quant_tree = None
            self._decode = jax.jit(self.model.decode_step)
            self._prefill = jax.jit(self.model.prefill)
        self.stats = {"prefill_tokens": 0, "decode_tokens": 0,
                      "prefill_s": 0.0, "decode_s": 0.0, "rejected": 0,
                      "truncated": 0}

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        if self.temperature <= 0:
            return np.argmax(logits, axis=-1)
        z = logits / self.temperature
        z = z - z.max(-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(-1, keepdims=True)
        return np.array([self.rng.choice(p.shape[-1], p=pi) for pi in p])

    def run(self, requests: list) -> list:
        """Serve a list of Requests to completion; returns them filled."""
        queue = []
        for r in requests:
            verdict, eff = kvcache.admit(len(r.prompt), self.max_context,
                                         self.admission)
            if verdict == ADMIT_REJECT:
                r.status, r.done = "rejected", True
                self.stats["rejected"] += 1
                continue
            if verdict == ADMIT_TRUNCATE:
                r.prompt = np.asarray(r.prompt)[-eff:]
                r.truncated = True
                self.stats["truncated"] += 1
            queue.append(r)
        while queue:
            batch = queue[:self.max_batch]
            queue = queue[self.max_batch:]
            self._serve_batch(batch)
        return requests

    def _serve_batch(self, batch: list):
        B = len(batch)
        S = max(len(r.prompt) for r in batch)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(batch):
            toks[i, S - len(r.prompt):] = r.prompt     # left-pad
        t0 = time.time()
        logits, cache = self._prefill(self.params, {"tokens": toks})
        self.stats["prefill_s"] += time.time() - t0
        self.stats["prefill_tokens"] += int(B * S)
        # embed prefill KV into the serving context window (dense/moe: the
        # "k"/"v" caches are (L,B,S,H,D); SSM states are fixed-size and pass
        # through untouched)
        if isinstance(cache, dict):
            cache = {k: (self._pad_kv(v) if k in ("k", "v") else v)
                     for k, v in cache.items()}
        last = self._sample(np.asarray(logits)[:, -1])
        for i, r in enumerate(batch):
            r.out_tokens.append(int(last[i]))
            if r.on_token is not None:
                r.on_token(r.rid, len(r.out_tokens) - 1, r.out_tokens[-1])
        max_new = max(min(r.max_new_tokens, self.max_context + 1 - S)
                      for r in batch)
        t0 = time.time()
        for t in range(1, max_new):
            pos = jnp.int32(S + t - 1)
            lg, cache = self._decode(self.params, cache,
                                     jnp.asarray(last[:, None], jnp.int32),
                                     pos)
            last = self._sample(np.asarray(lg)[:, 0])
            self.stats["decode_tokens"] += B
            for i, r in enumerate(batch):
                if not r.done and len(r.out_tokens) < r.max_new_tokens:
                    tok = int(last[i])
                    r.out_tokens.append(tok)
                    if r.on_token is not None:
                        r.on_token(r.rid, len(r.out_tokens) - 1, tok)
                    if tok == self.eos_id:
                        r.done = True
            if all(r.done or len(r.out_tokens) >= r.max_new_tokens
                   for r in batch):
                break
        self.stats["decode_s"] += time.time() - t0
        for r in batch:
            r.done = True
            r.status = "done"

    def _pad_kv(self, leaf):
        """Grow a prefill KV cache (L,B,S,H,D) to the serving context."""
        if leaf.shape[2] < self.max_context:
            pad = [(0, 0)] * leaf.ndim
            pad[2] = (0, self.max_context - leaf.shape[2])
            return jnp.pad(leaf, pad)
        return leaf
