"""Execution layer of the Track-A round engine (DESIGN.md §7, §8, §9).

`RoundExecutor` is the fused flat-parameter round step — chunked,
plan-shaped (ragged) or uniform-cap (masked), optionally sharded — operating
on a `repro.fl.state.ClientStateStore` row pool instead of a dense
[n_clients, n_params] buffer: `step`/`step_ragged` resolve the round's
participants to pool slots (``store.prepare``, main thread — the pool is
donated through the in-flight jitted step), run the donated step on
``store.pool``/``store.ef_pool``, and hand the fresh buffers back
(``store.adopt``). All gather/scatter indices inside the jitted code are
pool SLOTS; the pad index is ``store.capacity`` (out of range ⇒ the scatter
drops it and the clamped gather row is masked out and written back
unchanged). Shard bodies derive their row offset from the block-local pool
shape, so pool growth (a pow2 resize + jit recompile) needs no rebuild.

bf16 pools scatter through **stochastic rounding**
(`core.compression.stochastic_round_cast`, ``SimConfig.stochastic_round``):
each round/chunk folds a SeedSequence-derived seed (spawn key (3, t, i) —
kinds 0/1/2 belong to the capability/sampling streams) into the downcast so
quantization error is zero-mean noise instead of a per-round bias.
Exactly-representable values are SR fixed points, so masked/padded rows
stay bit-unchanged. f32 pools are untouched (cast is the identity).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import batchsize as BS
from repro.core import compression as C
from repro.core import rng as RNG
from repro.fl.robust import weighted_row_fold
from repro.fl.spans import span
from repro.launch import mesh as MESH

BUFFER_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# extra f32 [chunk, n_params] arrays the EF carry keeps live in the round
# step (gathered residual rows + recomputed residuals) — auto_chunk input
EF_EXTRA_ARRAYS = 2.0


@dataclasses.dataclass
class TierGroup:
    """One occupied (b, τ) execution tier of a round (DESIGN.md §8).

    ``pos`` are positions into the round's ``parts`` array (processing
    order); the batch arrays hold ``g_pad = tier_layout(len(pos))[0]`` rows
    — tail rows beyond ``len(pos)`` are zero-filled padding that the
    executor masks out (zero weight, out-of-range scatter index)."""
    b: int
    tau: int
    pos: np.ndarray           # [g] positions into parts
    g_pad: int
    slices: list              # [(start, chunk_rung)] from tier_layout
    xs: np.ndarray            # [g_pad, tau, b, ...feat]
    ys: np.ndarray            # [g_pad, tau, b]
    ws: np.ndarray            # [g_pad, tau, b] sample weights
    ims: np.ndarray           # [g_pad, tau] iteration masks


class RoundExecutor:
    """The fused flat-parameter round step over a ClientStateStore pool.

    **Masked** (``cfg.ragged=False``): one jitted step per pool shape
    (donated [n_params] global vector + [capacity, n_params] pool + EF
    pool). Internally a lax.scan over fixed-size participant chunks
    carries (pool, EF pool, upload-sum): each chunk gathers its rows, runs
    the vmapped per-participant round at the [τ, b_max] cap, masks its
    upload contribution into the accumulator and scatters its rows back —
    so only [chunk, n_params] intermediates are ever live.

    **Ragged** (default, DESIGN.md §8): the host groups participants by
    quantized (b, τ) tier and `step_ragged` runs a python loop of jitted
    **tier-chunk steps** — the same per-participant math at the tier's
    ``[chunk_rung, τ_tier, b_tier]`` shape, threading the donated (pool,
    EF pool, upload accumulator) through every call, so the total is a
    left-fold over the processing order exactly like the masked scan.
    jax.jit caches one executable per distinct shape; shapes are drawn
    from the tier lattice × a power-of-two chunk-rung ladder
    (`tier_layout`) × the (pow2-bounded) pool-capacity ladder, so the
    cache is bounded by ``shape_lattice_bound()`` per capacity regardless
    of round count (telemetry via `telemetry()`).

    ``chunk_size=None`` resolves the chunk via `core.compression.
    auto_chunk` against ``chunk_budget_mb``, counting the EF carry
    (``EF_EXTRA_ARRAYS`` per-chunk f32 arrays) when error feedback is on.
    In sharded mode the masked scan runs inside a shard_map over the 1-D
    "data" mesh (upload sums cross shards with a psum) and the ragged
    tier-chunk step runs shard_mapped with per-shard tier groups padded to
    a common rung (per-shard partial upload sums, reduced at finalize); the
    pool's per-shard slot segments replace the old per-shard client rows.
    On a multi-process (multi-host) mesh the grouped inputs are assembled
    per process (`launch.mesh.host_local_array`) and the per-participant
    outputs allgathered (`launch.mesh.fetch_global`); the device math is
    identical.

    The error-feedback residual (``CaesarConfig.use_error_feedback``) rides
    the same machinery: a [capacity, ef_width] pool whose rows are
    gathered/scattered alongside the local models, ``ef_width = n_params``
    when EF is on and 0 when off — the disabled path carries a zero-width
    buffer, so there is no silent no-op and the residual adds no cost
    unless enabled. The pool may be stored ``bfloat16``
    (``SimConfig.buffer_dtype``): gathers upcast to f32 for compute,
    scatters downcast (stochastically rounded by default) — for f32 the
    casts are identities.
    """

    def __init__(self, cfg, apply_fn, spec: C.FlatSpec,
                 backend: str, quantize: bool, n_part: int, mesh=None,
                 use_ef: bool = False):
        self.cfg = cfg
        self.apply_fn = apply_fn
        self.spec = spec
        self.backend = backend
        self.quantize = quantize
        self.use_ef = use_ef
        self.ef_width = spec.n_params if use_ef else 0
        self.mesh = mesh
        self.n_clients = cfg.n_clients
        if cfg.buffer_dtype not in BUFFER_DTYPES:
            raise ValueError(f"unknown buffer_dtype {cfg.buffer_dtype!r}; "
                             f"want one of {tuple(BUFFER_DTYPES)}")
        self.buf_dtype = BUFFER_DTYPES[cfg.buffer_dtype]
        self.use_sr = (self.buf_dtype == jnp.bfloat16
                       and getattr(cfg, "stochastic_round", True))
        self.n_dev = mesh.shape["data"] if mesh is not None else 1
        if n_part % self.n_dev:
            raise ValueError(f"participants ({n_part}) must divide evenly "
                             f"over {self.n_dev} shards")
        self.rows_per_shard = self.n_clients // self.n_dev
        self.p_shard = n_part // self.n_dev
        chunk_size = cfg.chunk_size
        if chunk_size is None:
            chunk_size = C.auto_chunk(
                spec.n_params, self.p_shard, cfg.chunk_budget_mb,
                extra_arrays=EF_EXTRA_ARRAYS if use_ef else 0.0)
        self.chunk, self.p_pad, self.n_chunks = C.chunk_layout(
            self.p_shard, chunk_size)
        self.b_cap, self.tau_cap = cfg.caesar.b_max, cfg.caesar.tau
        self.b_min = cfg.caesar.b_min
        # ragged telemetry: cumulative per-tier participant counts, the set
        # of tier-chunk shapes traced (≅ jit-cache entries), plan-shaped vs
        # cap work in participant·iteration·sample units
        self.tier_occupancy: dict = {}
        self._shapes_seen: set = set()
        self.work_ragged = 0
        self.work_cap = 0
        self._last_store = None
        self._build()

    # -- tier shape lattice -------------------------------------------------

    def chunk_rungs(self) -> list:
        """The static chunk-size ladder: {chunk} ∪ {powers of two < chunk}.
        Every tier-chunk call uses a rung, so the jit cache stays bounded."""
        rungs = {self.chunk}
        r = 1
        while r < self.chunk:
            rungs.add(r)
            r <<= 1
        return sorted(rungs)

    def tier_layout(self, g: int) -> tuple[int, list]:
        """Chunk-rung decomposition of a tier group of ``g`` participants:
        ⌊g/chunk⌋ full chunks plus a power-of-two tail rung covering the
        remainder (padding < remainder). Returns (g_pad, [(start, rung)])."""
        if g <= 0:
            raise ValueError(f"tier group must be non-empty, got {g}")
        k, r = divmod(g, self.chunk)
        slices = [(i * self.chunk, self.chunk) for i in range(k)]
        g_pad = k * self.chunk
        if r:
            rung = min(1 << (r - 1).bit_length(), self.chunk)
            slices.append((g_pad, rung))
            g_pad += rung
        return g_pad, slices

    def shape_lattice_bound(self) -> int:
        """Upper bound on distinct compiled tier-chunk shapes (per pool
        capacity): the (b, τ) tier lattice × the chunk-rung ladder."""
        return (BS.tier_lattice_size(self.b_min, self.b_cap, self.tau_cap)
                * len(self.chunk_rungs()))

    def telemetry(self) -> dict:
        occ = {f"b{b}xt{t}": int(n)
               for (b, t), n in sorted(self.tier_occupancy.items())}
        out = {"tier_occupancy": occ,
               "compiled_tier_shapes": len(self._shapes_seen),
               "shape_lattice_bound": self.shape_lattice_bound(),
               "work_fraction": (self.work_ragged / self.work_cap
                                 if self.work_cap else 1.0)}
        # eviction-error telemetry (ROADMAP item 1) is measured where the
        # restores happen — surface the store's numbers alongside the
        # executor's so benchmarks read one dict
        if self._last_store is not None:
            err = self._last_store.telemetry().get("restore_error")
            if err is not None:
                out["restore_error"] = err
        return out

    # -- RNG for the stochastic-rounding scatter ----------------------------

    def _round_seed(self, t: int, i: int = 0) -> np.uint32:
        """Per-(round, tier-chunk-call) SR seed. Spawn-key kind 3
        (repro.core.rng names the full registry); kinds 0/1 are the
        capability streams, 2 the round sampling stream — all hang off
        the same root seed, none collide."""
        return RNG.sequence(
            self.cfg.seed, RNG.KIND_SR_SCATTER, t, i).generate_state(1)[0]

    def _store_cast(self, x, key):
        """f32 → storage dtype for the pool scatter. SR when enabled;
        identity for f32 pools; round-to-nearest-even bf16 otherwise."""
        if self.use_sr:
            return C.stochastic_round_cast(x, self.buf_dtype, key)
        return x.astype(self.buf_dtype)

    # -- jit construction ---------------------------------------------------
    def _make_participant_round(self):
        """The per-participant round math, shared verbatim by the masked
        and ragged engines — shape-polymorphic in (τ, b)."""
        cfg = self.cfg
        apply_fn = self.apply_fn
        spec = self.spec
        backend = self.backend
        n_params = spec.n_params
        # scheme-level switches are fixed for the simulation → Python-level
        # branches, not lax.cond: the compiled step contains only one path.
        use_recovery = cfg.scheme == "caesar"
        quantize = self.quantize
        use_ef = self.use_ef

        def ce_loss(params, x, y, w):
            logits = apply_fn(params, x)
            logp = jax.nn.log_softmax(logits)
            ll = jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
            return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)

        def local_train(params, xs, ys, ws, iter_mask, lr):
            """τ masked SGD steps. xs [τ,b,...]; ws [τ,b]; iter_mask [τ]."""
            def step(p, inp):
                x, y, w, m = inp
                g = jax.grad(ce_loss)(p, x, y, w)
                newp = jax.tree.map(lambda a, b_: a - lr * m * b_, p, g)
                return newp, None
            out, _ = jax.lax.scan(step, params, (xs, ys, ws, iter_mask))
            return out

        def participant_round(global_f, g_cdf, g_max, local_f, ef_row, xs,
                              ys, ws, iter_mask, lr, theta_d, theta_u):
            """One participant, entirely on flat [n_params] vectors."""
            # --- download: per-device threshold is an O(1) lookup in the
            # shared global-model cdf (one histogram per ROUND, not per device)
            thr_d = C.threshold_from_cdf(g_cdf, g_max, theta_d)
            kept, sign, cnt, ssum, smax = C.fused_compress(global_f, thr_d,
                                                           backend)
            mean_abs = ssum / jnp.maximum(cnt, 1)
            # wire-format convention (kernels/ref.py): sign==0 marks a
            # full-precision slot. An exact-zero compressed weight therefore
            # arrives as its true value 0 (not the stale local) — a
            # zero-deviation difference from the pytree engine's mask form.
            if use_recovery:
                w_init = C.fused_recover(kept, sign, local_f, mean_abs, smax,
                                         backend)
            else:   # plain stale substitution on the compressed slots
                w_init = jnp.where(sign != 0, local_f, kept)
            down_bits = C.hybrid_payload_bits(n_params, cnt)
            # --- local training (pytree exists only inside apply_fn)
            w_fin = local_train(C.unflatten_vector(w_init, spec),
                                xs, ys, ws, iter_mask, lr)
            flat_fin = C.flatten_vector(w_fin, spec)
            delta = w_init - flat_fin
            gnorm = jnp.linalg.norm(delta)
            # --- upload (EF: compress the residual-corrected delta, stash
            # what the compressor dropped back into the participant's row)
            target = delta + ef_row if use_ef else delta
            thr_u = C.fused_threshold(target, theta_u, backend)
            if quantize:   # ProWD-style: 1-bit masked elements, sign·mean
                k2, s2, c2, ss2, mx2 = C.fused_compress(target, thr_u,
                                                        backend)
                up = jnp.where(s2 != 0,
                               s2.astype(jnp.float32)
                               * (ss2 / jnp.maximum(c2, 1)), k2)
                up_bits = C.hybrid_payload_bits(n_params, c2)
            else:          # top-k sparsification
                up, up_bits = C.topk_sparsify_at(target, thr_u)
            new_ef = target - up if use_ef else ef_row
            return up, flat_fin, new_ef, down_bits, up_bits, gnorm

        return participant_round

    def _build(self):
        participant_round = self._make_participant_round()
        self._build_masked(participant_round)
        self._build_ragged(participant_round)

    def _build_masked(self, participant_round):
        n_params = self.spec.n_params
        backend = self.backend
        chunk, n_chunks = self.chunk, self.n_chunks
        cast = self._store_cast

        def chunked_scan(global_f, g_cdf, g_max, buf, ef_buf, parts_l, pmask,
                         xs, ys, ws, ims, lr, theta_d, theta_u, seed):
            """Scan over participant chunks; carry = (pool, EF pool,
            upload-sum).

            ``parts_l`` are pool-SLOT indices [p_pad] (shard-local in
            sharded mode); padded entries carry an out-of-range index
            (scatter drops them, the clamped gather row is masked out of
            the upload sum and written back unchanged — an SR fixed
            point, so bit-unchanged under stochastic rounding too)."""
            def reshape_c(a):
                return a.reshape((n_chunks, chunk) + a.shape[1:])
            inp = tuple(map(reshape_c, (parts_l, pmask, xs, ys, ws, ims,
                                        theta_d, theta_u)))
            inp = inp + (jnp.arange(n_chunks, dtype=jnp.uint32),)
            base_key = jax.random.PRNGKey(seed)

            def chunk_step(carry, c):
                buf, ef_buf, up_sum = carry
                p_c, m_c, xs_c, ys_c, ws_c, ims_c, td_c, tu_c, c_i = c
                lp_raw = buf[p_c]                       # [chunk, n_params]
                lp_sel = lp_raw.astype(jnp.float32)
                ef_sel = ef_buf[p_c]                    # [chunk, ef_width]
                ups, new_lp, new_ef, db, ub, gn = jax.vmap(
                    participant_round,
                    in_axes=(None, None, None, 0, 0, 0, 0, 0, 0, None, 0,
                             0))(
                    global_f, g_cdf, g_max, lp_sel, ef_sel, xs_c, ys_c,
                    ws_c, ims_c, lr, td_c, tu_c)
                up_sum = up_sum + jnp.sum(ups * m_c[:, None], axis=0)
                buf = buf.at[p_c].set(
                    cast(jnp.where(m_c[:, None] > 0, new_lp, lp_sel),
                         jax.random.fold_in(base_key, c_i)))
                ef_buf = ef_buf.at[p_c].set(
                    jnp.where(m_c[:, None] > 0, new_ef, ef_sel))
                return (buf, ef_buf, up_sum), (db, ub, gn)

            (buf, ef_buf, up_sum), (db, ub, gn) = jax.lax.scan(
                chunk_step, (buf, ef_buf, jnp.zeros(n_params, jnp.float32)),
                inp)
            return (buf, ef_buf, up_sum, db.reshape(-1), ub.reshape(-1),
                    gn.reshape(-1))

        if self.mesh is None:
            def round_step(global_f, pool, ef_buf, parts, pmask, xs,
                           ys, ws, ims, lr, theta_d, theta_u, seed):
                g_cdf, g_max = C.fused_histogram_cdf(global_f, backend)
                buf, ef_buf, up_sum, db, ub, gn = chunked_scan(
                    global_f, g_cdf, g_max, pool, ef_buf, parts, pmask,
                    xs, ys, ws, ims, lr, theta_d, theta_u, seed)
                # aggregate (Algorithm 1 line 13) over the valid participants
                new_global = global_f - up_sum / jnp.maximum(jnp.sum(pmask),
                                                             1.0)
                return new_global, buf, ef_buf, db, ub, gn

            # donating the global vector and the [capacity, n_params]
            # pool/EF buffers lets XLA scatter the participants' rows in
            # place instead of copying the whole pool every round
            # (~60ms/round at 100×164k on CPU)
            self._round_step = jax.jit(round_step, donate_argnums=(0, 1, 2))
            return

        def shard_body(global_f, buf, ef_buf, parts, pmask,
                       xs, ys, ws, ims, lr, theta_d, theta_u, seed):
            # one global-model histogram per round, computed by every shard
            # on its replicated copy (a Pallas kernel cannot be partitioned
            # automatically, so it runs inside the shard_map)
            g_cdf, g_max = C.fused_histogram_cdf(global_f, backend)
            # global slot → shard-local pool row; the segment size comes
            # from the block-local pool shape, so pool growth (a new jit
            # trace) needs no rebuild. Padding (= capacity) stays out of
            # range for every shard.
            row0 = jax.lax.axis_index("data") * buf.shape[0]
            parts_l = parts - row0
            buf, ef_buf, up_sum, db, ub, gn = chunked_scan(
                global_f, g_cdf, g_max, buf, ef_buf, parts_l, pmask, xs, ys,
                ws, ims, lr, theta_d, theta_u, seed)
            up_sum = jax.lax.psum(up_sum, "data")
            cnt = jax.lax.psum(jnp.sum(pmask), "data")
            new_global = global_f - up_sum / jnp.maximum(cnt, 1.0)
            return new_global, buf, ef_buf, db, ub, gn

        sharded = jax.shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(P(), P("data", None), P("data", None),
                      P("data"), P("data"), P("data"), P("data"), P("data"),
                      P("data"), P(), P("data"), P("data"), P()),
            out_specs=(P(), P("data", None), P("data", None), P("data"),
                       P("data"), P("data")),
            axis_names={"data"}, check_vma=False)
        self._round_step = jax.jit(sharded, donate_argnums=(0, 1, 2))

    def _build_ragged(self, participant_round):
        """The per-shape tier-chunk step (jax.jit caches one executable per
        [chunk_rung, τ_tier, b_tier] shape), plus the shared per-round
        histogram and the donated aggregation finalizer."""
        backend = self.backend
        cast = self._store_cast

        def tier_chunk(buf, ef_buf, up_sum, global_f, g_cdf, g_max, parts_l,
                       pmask, xs, ys, ws, ims, lr, theta_d, theta_u, seed):
            lp_raw = buf[parts_l]                   # [c, n_params]
            lp_sel = lp_raw.astype(jnp.float32)
            ef_sel = ef_buf[parts_l]                # [c, ef_width]
            ups, new_lp, new_ef, db, ub, gn = jax.vmap(
                participant_round,
                in_axes=(None, None, None, 0, 0, 0, 0, 0, 0, None, 0, 0))(
                global_f, g_cdf, g_max, lp_sel, ef_sel, xs, ys, ws, ims,
                lr, theta_d, theta_u)
            sel = pmask[:, None] > 0
            # association-fixed fold shared with the server-side
            # replay (fl/robust.py) — see weighted_row_fold
            up_sum = weighted_row_fold(up_sum, ups, pmask)
            buf = buf.at[parts_l].set(
                cast(jnp.where(sel, new_lp, lp_sel),
                     jax.random.PRNGKey(seed)))
            ef_buf = ef_buf.at[parts_l].set(jnp.where(sel, new_ef, ef_sel))
            return buf, ef_buf, up_sum, db, ub, gn

        if self.mesh is None:
            # unsharded rounds run tier_chunk_defer + this fold instead of
            # the fused tier_chunk — see step_ragged; the fused variant
            # stays the sharded path's kernel (one all-reduce per chunk)
            self._tier_chunk = None
            self._fold = jax.jit(weighted_row_fold, donate_argnums=(0,))
        else:
            def shard_body(buf, ef_buf, up_sum, global_f, g_cdf, g_max,
                           parts, pmask, xs, ys, ws, ims, lr, td, tu, seed):
                row0 = jax.lax.axis_index("data") * buf.shape[0]
                b, e, u, db, ub, gn = tier_chunk(
                    buf, ef_buf, up_sum[0], global_f, g_cdf, g_max,
                    parts - row0, pmask, xs, ys, ws, ims, lr, td, tu, seed)
                # per-shard partial upload sums ride a [n_dev, n_params]
                # "data"-sharded accumulator; the finalizer reduces them
                return b, e, u[None], db, ub, gn

            sm = jax.shard_map(
                shard_body, mesh=self.mesh,
                in_specs=(P("data", None), P("data", None), P("data", None),
                          P(), P(), P(), P("data"), P("data"), P("data"),
                          P("data"), P("data"), P("data"), P(), P("data"),
                          P("data"), P()),
                out_specs=(P("data", None), P("data", None),
                           P("data", None), P("data"), P("data"),
                           P("data")),
                axis_names={"data"}, check_vma=False)
            self._tier_chunk = jax.jit(sm, donate_argnums=(0, 1, 2))

        def tier_chunk_defer(buf, ef_buf, global_f, g_cdf, g_max, parts_l,
                             wmask, xs, ys, ws, ims, lr, theta_d, theta_u,
                             seed):
            """Wire-boundary twin of ``tier_chunk``: identical per-
            participant math and row writes, but the raw uploads come BACK
            [c, n_params] instead of folding into an accumulator — the
            server aggregates them after the serialize → transport →
            decode round trip (fl/robust.py replays the same fold, so the
            zero-fault result is bit-identical). ``wmask`` is the row-
            ADOPTION mask: a dropped participant trains but its pool/EF
            rows roll back (the server never saw the round)."""
            lp_raw = buf[parts_l]                   # [c, n_params]
            lp_sel = lp_raw.astype(jnp.float32)
            ef_sel = ef_buf[parts_l]                # [c, ef_width]
            ups, new_lp, new_ef, db, ub, gn = jax.vmap(
                participant_round,
                in_axes=(None, None, None, 0, 0, 0, 0, 0, 0, None, 0, 0))(
                global_f, g_cdf, g_max, lp_sel, ef_sel, xs, ys, ws, ims,
                lr, theta_d, theta_u)
            sel = wmask[:, None] > 0
            buf = buf.at[parts_l].set(
                cast(jnp.where(sel, new_lp, lp_sel),
                     jax.random.PRNGKey(seed)))
            ef_buf = ef_buf.at[parts_l].set(jnp.where(sel, new_ef, ef_sel))
            return buf, ef_buf, ups, db, ub, gn

        self._tier_chunk_defer = jax.jit(tier_chunk_defer,
                                         donate_argnums=(0, 1))

        def hist(g):
            return C.fused_histogram_cdf(g, backend)

        if self.mesh is not None:
            # the replicated global vector: every shard computes the same
            # histogram (a Pallas kernel is not partitioned automatically)
            hist = jax.shard_map(hist, mesh=self.mesh, in_specs=P(),
                                 out_specs=(P(), P()), check_vma=False)
        self._hist = jax.jit(hist)

        def finalize(global_f, up_sum, cnt):
            total = up_sum if up_sum.ndim == 1 else jnp.sum(up_sum, axis=0)
            return global_f - total / jnp.maximum(cnt, 1.0)

        self._finalize = jax.jit(finalize, donate_argnums=(0,))

    # -- host-side chunk/shard marshalling ----------------------------------
    def _group(self, a: np.ndarray, order: np.ndarray, fill) -> np.ndarray:
        """Order by shard, pad each shard's group to p_pad, flatten."""
        d, ps, pp = self.n_dev, self.p_shard, self.p_pad
        if d == 1 and pp == ps:
            # identity order, no padding: skip the fancy-index copy (tens
            # of MB per round for the batch tensors at dense cohorts)
            return np.asarray(a)
        a = np.asarray(a)[order].reshape((d, ps) + np.asarray(a).shape[1:])
        if pp > ps:
            a = np.concatenate(
                [a, np.full((d, pp - ps) + a.shape[2:], fill, a.dtype)],
                axis=1)
        return a.reshape((d * pp,) + a.shape[2:])

    def _ungroup(self, a, order: np.ndarray) -> np.ndarray:
        """Drop padding, restore the caller's participant order. Multi-host
        "data"-sharded outputs are allgathered into every process first."""
        d, ps, pp = self.n_dev, self.p_shard, self.p_pad
        a = MESH.fetch_global(a)
        a = a.reshape((d, pp) + a.shape[1:])
        a = a[:, :ps].reshape((d * ps,) + a.shape[2:])
        out = np.empty_like(a)
        out[order] = a
        return out

    def _put(self, a: np.ndarray, spec):
        """Device placement of one grouped host input. Single-process jit
        handles the (re)sharding itself; a multi-process mesh needs the
        global array assembled from each process's local rows."""
        if self.mesh is None or jax.process_count() == 1:
            return jnp.asarray(a)
        return MESH.host_local_array(self.mesh, spec, a)

    def _resolve_slots(self, store, parts: np.ndarray, t: int):
        """Activate the round's participants in the store (MAIN thread —
        the pool is donated through the in-flight step) and validate the
        sharded stratification. Returns (slots [P] i32, shard order)."""
        self._last_store = store
        parts = np.asarray(parts)
        owner = parts // self.rows_per_shard
        if self.n_dev > 1:
            counts = np.bincount(owner, minlength=self.n_dev)
            if not (counts == self.p_shard).all():
                raise ValueError(
                    "sharded mode needs stratified participants "
                    f"({self.p_shard} per shard; got {counts.tolist()})")
        with span("exec.prepare", round=t):
            slots = store.prepare(parts, t)
        # a client's slot lives in its own shard's segment, so the
        # client-shard order IS the slot-shard order
        return slots, np.argsort(owner, kind="stable")

    def step(self, global_f, store, parts: np.ndarray, xs, ys,
             ws, ims, lr, theta_d, theta_u, t: int = 0):
        """Run one MASKED round at the [τ, b_max] cap. Returns (global_f,
        down_bits [P], up_bits [P], gnorms [P]) with per-participant
        outputs as np arrays in the caller's ``parts`` order; the updated
        pool/EF rows land back in ``store``."""
        slots, order = self._resolve_slots(store, parts, t)
        g = lambda a, fill: self._put(self._group(a, order, fill),
                                      P("data"))
        with span("exec.dispatch", round=t, rows=self.n_dev * self.p_pad,
                  valid=len(parts)):
            new_global, new_pool, new_ef, db, ub, gn = self._round_step(
                global_f, store.pool, store.ef_pool,
                g(slots, np.int32(store.capacity)),
                g(np.ones(len(parts), np.float32), np.float32(0.0)),
                g(xs, xs.dtype.type(0)), g(ys, ys.dtype.type(0)),
                g(ws, np.float32(0.0)), g(ims, np.float32(0.0)), lr,
                g(theta_d, np.float32(0.0)), g(theta_u, np.float32(0.0)),
                jnp.uint32(self._round_seed(t)))
            store.adopt(new_pool, new_ef)
        with span("exec.readback", round=t):
            return (new_global, self._ungroup(db, order),
                    self._ungroup(ub, order), self._ungroup(gn, order))

    # -- ragged execution ---------------------------------------------------

    def _tier_chunks(self, tg: TierGroup, slots32: np.ndarray,
                     theta_d: np.ndarray, theta_u: np.ndarray,
                     pad_idx: int, cap_per_shard: int):
        """Yield (positions, out_slots, device-input dict) per tier chunk.

        ``slots32`` are the participants' POOL slots (parts order);
        ``pad_idx`` (= store capacity) is the out-of-range scatter index
        padding carries. Single-device: zero-copy views over the (already
        rung-padded) tier arrays. Sharded: each shard's tier members are
        regrouped shard-major and padded to a common rung decomposition
        (tier membership is capability-driven, so per-shard counts
        differ); positions/out_slots map the [n_dev·c] outputs back to
        valid participants."""
        pad = np.int32(pad_idx)
        g = len(tg.pos)
        if self.n_dev == 1:
            for s, c in tg.slices:
                pos_c = tg.pos[s:min(s + c, g)]
                v = len(pos_c)
                pc = np.full(c, pad, np.int32)
                pc[:v] = slots32[pos_c]
                pm = np.zeros(c, np.float32)
                pm[:v] = 1.0
                td = np.zeros(c, np.float32)
                td[:v] = theta_d[pos_c]
                tu = np.zeros(c, np.float32)
                tu[:v] = theta_u[pos_c]
                yield pos_c, np.arange(v), dict(
                    parts=pc, pmask=pm, xs=tg.xs[s:s + c], ys=tg.ys[s:s + c],
                    ws=tg.ws[s:s + c], ims=tg.ims[s:s + c], td=td, tu=tu)
            return
        d = self.n_dev
        owner = slots32[tg.pos] // cap_per_shard
        iloc = [np.flatnonzero(owner == s) for s in range(d)]
        length = max(len(il) for il in iloc)
        l_pad, slices = self.tier_layout(length)
        sel = np.full((d, l_pad), -1, np.int64)
        for s_i, il in enumerate(iloc):
            sel[s_i, :len(il)] = il
        for s, c in slices:
            sc = sel[:, s:s + c].reshape(-1)
            valid = sc >= 0
            pos_c = tg.pos[sc[valid]]
            pc = np.full(d * c, pad, np.int32)
            pc[valid] = slots32[pos_c]
            pm = valid.astype(np.float32)
            td = np.zeros(d * c, np.float32)
            td[valid] = theta_d[pos_c]
            tu = np.zeros(d * c, np.float32)
            tu[valid] = theta_u[pos_c]

            def take(a):
                out = np.zeros((d * c,) + a.shape[1:], a.dtype)
                out[valid] = a[sc[valid]]
                return out

            yield pos_c, np.flatnonzero(valid), dict(
                parts=pc, pmask=pm, xs=take(tg.xs), ys=take(tg.ys),
                ws=take(tg.ws), ims=take(tg.ims), td=td, tu=tu)

    def step_ragged(self, global_f, store, parts: np.ndarray,
                    tiers: list, lr, theta_d, theta_u, t: int = 0):
        """Run one PLAN-SHAPED round: one jitted chunk step per occupied
        tier shape, threading the donated (pool, EF pool, upload
        accumulator) through every call. Same return contract as `step`."""
        n = len(parts)
        n_params = self.spec.n_params
        slots32, _ = self._resolve_slots(store, parts, t)
        with span("exec.dispatch", round=t) as sp:
            g_cdf, g_max = self._hist(global_f)
            if self.mesh is None:
                up_sum = jnp.zeros(n_params, jnp.float32)
            else:
                up_sum = self._put(np.zeros((self.n_dev, n_params),
                                            np.float32), P("data", None))
            buf, ef = store.pool, store.ef_pool
            pend = []
            call_i = rows = 0
            for tg in tiers:
                key = (int(tg.b), int(tg.tau))
                self.tier_occupancy[key] = (self.tier_occupancy.get(key, 0)
                                            + len(tg.pos))
                for pos_c, slots, a in self._tier_chunks(
                        tg, slots32, theta_d, theta_u,
                        pad_idx=store.capacity,
                        cap_per_shard=store.cap_per_shard):
                    # count the rows actually executed (the sharded path
                    # re-pads tiers to a cross-shard rung, exceeding the
                    # tier's g_pad)
                    rows += len(a["parts"])
                    self.work_ragged += len(a["parts"]) * tg.tau * tg.b
                    self._shapes_seen.add((len(a["parts"]) // self.n_dev,
                                           int(tg.tau), int(tg.b)))
                    if self.mesh is None:
                        # single compiled kernel shared with the wire path:
                        # tier_chunk_defer + the association-fixed fold —
                        # one XLA module either way, so wire replay
                        # bit-identity holds by construction, not by fusion
                        # luck
                        pmask = jnp.asarray(a["pmask"])
                        buf, ef, ups, db, ub, gn = self._tier_chunk_defer(
                            buf, ef, global_f, g_cdf, g_max,
                            jnp.asarray(a["parts"]), pmask,
                            jnp.asarray(a["xs"]), jnp.asarray(a["ys"]),
                            jnp.asarray(a["ws"]), jnp.asarray(a["ims"]), lr,
                            jnp.asarray(a["td"]), jnp.asarray(a["tu"]),
                            jnp.uint32(self._round_seed(t, call_i)))
                        up_sum = self._fold(up_sum, ups, pmask)
                        call_i += 1
                        pend.append((pos_c, slots, db, ub, gn))
                        continue
                    buf, ef, up_sum, db, ub, gn = self._tier_chunk(
                        buf, ef, up_sum, global_f, g_cdf, g_max,
                        self._put(a["parts"], P("data")),
                        self._put(a["pmask"], P("data")),
                        self._put(a["xs"], P("data")),
                        self._put(a["ys"], P("data")),
                        self._put(a["ws"], P("data")),
                        self._put(a["ims"], P("data")), lr,
                        self._put(a["td"], P("data")),
                        self._put(a["tu"], P("data")),
                        jnp.uint32(self._round_seed(t, call_i)))
                    call_i += 1
                    pend.append((pos_c, slots, db, ub, gn))
            store.adopt(buf, ef)
            self.work_cap += n * self.tau_cap * self.b_cap
            new_global = self._finalize(global_f, up_sum, np.float32(n))
            sp.set_metadata(rows=rows, valid=n)
        db_o = np.empty(n, np.float32)
        ub_o = np.empty(n, np.float32)
        gn_o = np.empty(n, np.float32)
        with span("exec.readback", round=t):
            for pos_c, slots, db, ub, gn in pend:
                db_o[pos_c] = MESH.fetch_global(db)[slots]
                ub_o[pos_c] = MESH.fetch_global(ub)[slots]
                gn_o[pos_c] = MESH.fetch_global(gn)[slots]
        return new_global, db_o, ub_o, gn_o

    def step_ragged_deferred(self, global_f, store, parts: np.ndarray,
                             tiers: list, lr, theta_d, theta_u,
                             t: int = 0, wmask=None):
        """Wire-boundary variant of `step_ragged` (DESIGN.md §11): runs the
        identical tier-chunk stream but DEFERS aggregation — each chunk's
        raw uploads come back [c, n_params] for the caller to serialize,
        transport and fold server-side (fl/robust.py replays the same
        chunk-ordered accumulate, so a zero-fault round is bit-identical).

        ``wmask`` [P] bool (parts order) gates row adoption: participants
        whose upload the server never aggregates (dropouts, discarded
        stragglers, double-corrupted payloads) keep their pre-round
        pool/EF rows. Returns (chunks, down_bits, up_bits, gnorms) where
        ``chunks`` is the ordered list of (pos_c, valid_rows, c, ups) the
        server must replay. Unsharded only (the wire boundary serializes
        per client; a sharded wire engine would need per-shard servers)."""
        if self.mesh is not None:
            raise NotImplementedError("the wire-boundary round is "
                                      "single-mesh (set sharded=False)")
        n = len(parts)
        wm = (np.ones(n, np.float32) if wmask is None
              else np.asarray(wmask, np.float32))
        slots32, _ = self._resolve_slots(store, parts, t)
        with span("exec.dispatch", round=t) as sp:
            g_cdf, g_max = self._hist(global_f)
            buf, ef = store.pool, store.ef_pool
            chunks = []
            call_i = rows = 0
            for tg in tiers:
                key = (int(tg.b), int(tg.tau))
                self.tier_occupancy[key] = (self.tier_occupancy.get(key, 0)
                                            + len(tg.pos))
                for pos_c, slots, a in self._tier_chunks(
                        tg, slots32, theta_d, theta_u,
                        pad_idx=store.capacity,
                        cap_per_shard=store.cap_per_shard):
                    c = len(a["parts"])
                    rows += c
                    self.work_ragged += c * tg.tau * tg.b
                    self._shapes_seen.add((c, int(tg.tau), int(tg.b)))
                    wm_c = np.zeros(c, np.float32)
                    wm_c[slots] = wm[pos_c]
                    buf, ef, ups, db, ub, gn = self._tier_chunk_defer(
                        buf, ef, global_f, g_cdf, g_max,
                        jnp.asarray(a["parts"]), jnp.asarray(wm_c),
                        jnp.asarray(a["xs"]), jnp.asarray(a["ys"]),
                        jnp.asarray(a["ws"]), jnp.asarray(a["ims"]), lr,
                        jnp.asarray(a["td"]), jnp.asarray(a["tu"]),
                        jnp.uint32(self._round_seed(t, call_i)))
                    call_i += 1
                    chunks.append((pos_c, slots, c, ups, db, ub, gn))
            store.adopt(buf, ef)
            self.work_cap += n * self.tau_cap * self.b_cap
            sp.set_metadata(rows=rows, valid=n)
        db_o = np.empty(n, np.float32)
        ub_o = np.empty(n, np.float32)
        gn_o = np.empty(n, np.float32)
        # end-of-round readback: every chunk step has been submitted, so
        # these syncs drain the device queue, not stall mid-round
        with span("exec.readback", round=t):
            for pos_c, slots, _c, _ups, db, ub, gn in chunks:
                db_o[pos_c] = np.asarray(db)[slots]  # repro: noqa=REP006
                ub_o[pos_c] = np.asarray(ub)[slots]  # repro: noqa=REP006
                gn_o[pos_c] = np.asarray(gn)[slots]  # repro: noqa=REP006
        return ([(p, s, c, u) for p, s, c, u, *_ in chunks],
                db_o, ub_o, gn_o)
