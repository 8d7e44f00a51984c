"""Pipelined round driver for Track A (paper Algorithm 1; DESIGN.md §1,
§7–§9).

This module owns the orchestration shell of the layered round engine:

* `SimConfig` — the one simulation config consumed by every layer;
* `History` — eval-aligned metric series + per-round raw samples;
* `RoundPkg` — one round's prefetched inputs (participants, capability
  snapshot, plan, tier- or cap-shaped batches);
* `Simulator` — builds data/partition/capability/planner/executor, creates
  the per-run `repro.fl.state.ClientStateStore` row pool, and runs the
  (optionally pipelined) round loop with Eq.-7 time/waiting accounting and
  payload-faithful traffic accounting.

The layers it drives live in sibling modules: `repro.fl.planner`
(RoundPlanner), `repro.fl.executor` (RoundExecutor + TierGroup),
`repro.fl.state` (ClientStateStore). `repro.fl.simulation` re-exports
everything as the stable public surface.

Pipelining contract: host producer work for round t+1 runs on a worker
thread while the device executes round t. Every round draws from its own
``np.random.SeedSequence(seed, spawn_key=(2, t))`` stream and the batch-
index draw is always cap-shaped (plan-independent), so the pipelined and
synchronous (``SimConfig.pipelined=False``) loops consume identical
randomness and are same-seed identical. The worker NEVER touches the
state store — slot activation/eviction happens on the main thread inside
the executor step (the pool is donated through the in-flight jitted step;
a worker-side mutation would race the device).

Client splits are held CSR-style (one flat index array + offsets) rather
than as a per-client list: at 100k–1M registered clients the list-of-arrays
overhead (~100 B/client) would rival the sample data itself.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import batchsize as BS
from repro.core import caesar as CA
from repro.core import compression as C
from repro.core import rng as RNG
from repro.data import partition, synthetic
from repro.fl import availability as AV
from repro.fl import baselines as BL
from repro.fl import faults as F
from repro.fl import robust as RB
from repro.fl import wire as W
from repro.fl.capability import CapabilityModel
from repro.fl.executor import RoundExecutor, TierGroup
from repro.fl.planner import RoundPlanner
from repro.fl.spans import span
from repro.fl.state import ClientStateStore
from repro.launch import mesh as MESH
from repro.models import paper_models as PM
from repro.optim import sgd as SGD


@dataclasses.dataclass(frozen=True)
class SimConfig:
    dataset: str = "cifar10"
    model: Optional[str] = None          # default: paper pairing
    scheme: str = "caesar"               # caesar | fedavg | fic | cac | flexcom | prowd | pyramidfl
    n_clients: int = 100
    participation: float = 0.1
    rounds: int = 100
    p_heterogeneity: float = 5.0         # paper's p = 1/δ (default 5)
    data_scale: float = 0.05             # dataset size multiplier (CPU budget)
    eval_every: int = 5
    eval_samples: int = 1000
    seed: int = 0
    caesar: CA.CaesarConfig = dataclasses.field(default_factory=CA.CaesarConfig)
    sgd: SGD.SGDConfig = dataclasses.field(default_factory=SGD.SGDConfig)
    target_accuracy: Optional[float] = None
    # compression-operator backend: auto | pallas | interpret | jnp
    backend: str = "auto"
    # execution layer (DESIGN.md §7): participants per chunk. None ⇒
    # auto-tuned from n_params, the cohort, chunk_budget_mb and the EF carry
    # (core.compression.auto_chunk); 0 ⇒ one chunk of all participants (the
    # PR-1 single-vmap engine); an int bounds the per-round [P, n_params]
    # working set at chunk_size × n_params.
    chunk_size: Optional[int] = None
    # host working-set budget (MB) the auto-tuned chunk targets; ignored
    # when chunk_size is given explicitly.
    chunk_budget_mb: float = 1024.0
    # overlap host batch sampling for round t+1 with the device step for
    # round t (worker thread; same-seed identical to the synchronous loop —
    # every round owns a SeedSequence-derived RNG stream either way).
    pipelined: bool = True
    # plan-shaped ragged execution (DESIGN.md §8): run each participant at
    # its quantized (b, τ) tier shape instead of the [τ, b_max] cap with
    # zero-weight masks. False keeps the uniform-cap masked engine — the
    # parity baseline for the ragged-vs-masked CI gate.
    ragged: bool = True
    # storage dtype of the client-state pool rows. "bfloat16" halves the
    # pool; compute stays f32 (gather upcasts, scatter downcasts — see
    # `stochastic_round`), so this is a memory/accuracy trade, NOT
    # same-seed identical to f32.
    buffer_dtype: str = "float32"
    # client-state pool sizing (DESIGN.md §9): None ⇒ grow on demand with
    # the ever-participated cohort (no eviction — bit-identical to the
    # dense buffer); 0 ⇒ dense [n_clients] pool (exact legacy semantics
    # and footprint); int > 0 ⇒ hard row cap with staleness-tiered LRU
    # eviction onto cluster centroids (must cover the per-round cohort).
    state_capacity: Optional[int] = None
    # what eviction does with the exact row: "none" keeps only the
    # staleness-tier centroid; "host"/"memmap" additionally spill the
    # exact row (numpy / on-disk) so re-activation is exact paging.
    state_offload: str = "none"
    # directory for "memmap" spill files (default: a fresh temp dir)
    state_dir: Optional[str] = None
    # bf16 pools: stochastically round the scatter downcast (unbiased,
    # per-round seed) instead of round-to-nearest-even. No effect at f32.
    stochastic_round: bool = True
    # shard the client-state pool + participant chunks over the "data"
    # mesh (DESIGN.md §7). Requires n_clients divisible by the device
    # count; participants are drawn stratified per shard so every device
    # owns its participants' pool rows.
    sharded: bool = False
    # initialize jax.distributed and build the "data" mesh over every
    # host's devices (process-local pool rows, psum unchanged). Requires
    # sharded=True; a no-op single-process falls back to the local mesh.
    multi_host: bool = False
    # preliminary-study variants (Fig. 1): compress only one direction
    fic_down_only: bool = False
    fic_up_only: bool = False
    # synthetic-task difficulty overrides (e.g. {"sep": 2.0, "noise": 1.0})
    dataset_kwargs: Optional[dict] = None
    # --- wire-boundary fault engine (DESIGN.md §11) -----------------------
    # "inproc" keeps the legacy in-process aggregate; "loopback" serializes
    # every upload through the wire codec + an in-process FIFO (bit-
    # identical at zero faults — CI-gated); "queue" uses a multiprocessing
    # queue. Faults and non-mean aggregation REQUIRE a wire (they act on
    # serialized payloads).
    wire: str = "inproc"
    # fault injection rates (dropout/straggler/corruption/Byzantine); only
    # honored when wire != "inproc"
    faults: F.FaultConfig = dataclasses.field(default_factory=F.FaultConfig)
    # server aggregation policy: mean | trimmed_mean | norm_clip
    aggregation: str = "mean"
    # trimmed_mean: fraction of the cohort trimmed from EACH extreme
    trim_frac: float = 0.1
    # norm_clip: clip threshold C (None ⇒ per-round median upload norm)
    clip_norm: Optional[float] = None
    # wire value payload precision: float32 (exact) | bfloat16 (half the
    # value bytes, lossy — NOT bit-identical to inproc)
    wire_value_dtype: str = "float32"
    # record ||restored − true||/||true|| at every centroid restore
    # (ROADMAP item 1); surfaced via executor.telemetry()["restore_error"]
    measure_eviction_error: bool = False
    # --- trace-driven availability (DESIGN.md §12) -----------------------
    # who is samplable each round: "always" is the paper's world (uniform
    # draw over every client — byte-identical to the legacy driver, which
    # the bit-identity gate depends on); "diurnal" gates the draw on a
    # deterministic replayable timezone/session schedule (fl/availability)
    availability: AV.AvailabilityConfig = dataclasses.field(
        default_factory=AV.AvailabilityConfig)
    # krum only: assumed attacker count f (None ⇒ round(trim_frac·cohort))
    # and multi-Krum selection size m (None ⇒ cohort − f − 2)
    krum_f: Optional[int] = None
    krum_m: Optional[int] = None


@dataclasses.dataclass
class History:
    """Eval-aligned series: every list below has one entry per eval round
    (``rounds[i]`` is the round number of entry i). ``waiting`` is a RUNNING
    MEAN over all rounds simulated so far. Per-round raw samples live in the
    ``*_per_round`` lists; ``wall_per_round[0]`` carries the one-time XLA
    compile, and under the ragged engine later rounds that first touch a
    new tier shape also pay a compile inside their wall sample — the median
    after round 1, not a mean, is the robust per-round statistic."""
    rounds: list = dataclasses.field(default_factory=list)
    sim_time: list = dataclasses.field(default_factory=list)      # cumulative s
    traffic_bits: list = dataclasses.field(default_factory=list)  # cumulative
    accuracy: list = dataclasses.field(default_factory=list)
    waiting: list = dataclasses.field(default_factory=list)       # running mean s
    waiting_per_round: list = dataclasses.field(default_factory=list)
    wall_per_round: list = dataclasses.field(default_factory=list)
    # wire engine only: cumulative SERIALIZED bytes×8 actually sent
    # (headers, bitpacked indices, CRC, retransmissions) — the measured
    # counterpart of the modeled ``traffic_bits``; empty under "inproc"
    wire_bits: list = dataclasses.field(default_factory=list)

    def summary(self) -> dict:
        return {"final_acc": self.accuracy[-1] if self.accuracy else 0.0,
                "total_time_s": self.sim_time[-1] if self.sim_time else 0.0,
                "total_traffic_gb": (self.traffic_bits[-1] / 8e9
                                     if self.traffic_bits else 0.0)}

    def to_target(self, acc: float):
        """(time_s, traffic_gb, round) when ``acc`` first reached, else None."""
        for r, t, tr, a in zip(self.rounds, self.sim_time, self.traffic_bits,
                               self.accuracy):
            if a >= acc:
                return t, tr / 8e9, r
        return None


@dataclasses.dataclass
class RoundPkg:
    """Everything the driver needs to execute one round, produced by the
    prefetch path (worker thread when pipelined). ``plan`` and ``tiers``
    are filled for Caesar (whose planner is execution-independent);
    baseline policies plan on the main thread from ``xs``/``ys``."""
    parts: np.ndarray
    mu: np.ndarray
    bw_d: np.ndarray
    bw_u: np.ndarray
    plan: Optional[tuple] = None      # (theta_d, theta_u, batch, taus) [P]
    xs: Optional[np.ndarray] = None   # cap-shaped [P, τ, b_max, ...]
    ys: Optional[np.ndarray] = None
    tiers: Optional[list] = None      # list[TierGroup]
    fplan: Optional[F.FaultPlan] = None   # wire engine: round fault draw
    n_eligible: int = 0               # availability: online client count
    n_forced: int = 0                 # cohort shortfall force-woken


def stratified_cohort(rng: np.random.Generator, n_clients: int, n_part: int,
                      n_shards: int) -> np.ndarray:
    """The always-available cohort draw: uniform over all clients on one
    shard; with ``n_shards > 1``, ``n_part / n_shards`` clients drawn from
    each shard's contiguous block of client ids, so every device owns its
    participants' pool rows."""
    if n_shards <= 1:
        return rng.choice(n_clients, n_part, replace=False)
    rows, ps = n_clients // n_shards, n_part // n_shards
    return np.concatenate([
        rng.choice(np.arange(s * rows, (s + 1) * rows), ps, replace=False)
        for s in range(n_shards)])


# ---------------------------------------------------------------------------
# The simulator: orchestration + accounting
# ---------------------------------------------------------------------------

class Simulator:
    def __init__(self, cfg: SimConfig):
        self.cfg = cfg
        if cfg.multi_host and not cfg.sharded:
            raise ValueError("multi_host=True requires sharded=True (the "
                             "multi-host mesh is the sharded 'data' axis)")
        if cfg.multi_host:
            # MUST precede every jax call in this process (backend resolve,
            # param init): jax.distributed.initialize refuses to run after
            # the backends are up. Single-process (no cluster) falls back
            # cleanly, but say so — N processes silently simulating in
            # isolation would look like a successful multi-host run.
            if not MESH.init_distributed():
                warnings.warn(
                    "multi_host=True but no multi-process jax runtime was "
                    "detected (or jax was already initialized); running "
                    "single-process on the local devices", stacklevel=2)
        self.backend = C.resolve_backend(cfg.backend)
        ds_fn = synthetic.DATASETS[cfg.dataset]
        self.data = ds_fn(seed=cfg.seed, scale=cfg.data_scale,
                          **(cfg.dataset_kwargs or {}))
        model_name = cfg.model or PM.DATASET_MODEL[cfg.dataset]
        init_fn, self.apply_fn = PM.MODELS[model_name]
        feat_kw = {}
        if model_name == "lr":
            feat_kw = {"n_features": self.data.x_train.shape[-1]}
        self.params0 = init_fn(jax.random.PRNGKey(cfg.seed),
                               n_classes=self.data.n_classes, **feat_kw)
        # flatten ONCE: the engine state is flat from here on
        self.flat0, self.spec = C.flatten_tree(self.params0)
        self.n_params = self.spec.n_params
        self.model_bits = self.n_params * C.FULL_BITS

        splits, label_dist, volumes = partition.dirichlet_partition(
            self.data.y_train, cfg.n_clients, cfg.p_heterogeneity, cfg.seed)
        # CSR storage: the per-client list-of-arrays costs ~100 B/client of
        # pure object overhead — real money at 100k–1M registered clients
        self._split_off = np.zeros(cfg.n_clients + 1, np.int64)
        self._split_off[1:] = np.cumsum([len(s) for s in splits])
        self._split_idx = np.concatenate(splits).astype(np.int64)
        del splits
        self.volumes = volumes
        self.label_dist = label_dist
        self.cap = CapabilityModel(cfg.n_clients, cfg.seed)

        self.mesh = MESH.make_data_mesh() if cfg.sharded else None
        self.n_dev = self.mesh.shape["data"] if self.mesh is not None else 1
        if cfg.n_clients % self.n_dev:
            raise ValueError(f"n_clients ({cfg.n_clients}) must divide over "
                             f"{self.n_dev} shards")
        n_part = max(1, int(round(cfg.participation * cfg.n_clients)))
        # sharded rounds need equal per-shard cohorts (static shapes)
        self.n_part = max(self.n_dev, (n_part // self.n_dev) * self.n_dev)
        if self.n_part != n_part:
            warnings.warn(
                f"sharded mode adjusted the cohort from {n_part} to "
                f"{self.n_part} participants/round ({self.n_dev} shards "
                "need equal per-shard cohorts); pick a participation whose "
                "cohort divides the device count to silence this",
                stacklevel=2)

        self.policy = None if cfg.scheme == "caesar" else \
            self._make_policy(cfg.scheme)
        self.planner = RoundPlanner(cfg, volumes, label_dist,
                                    self.model_bits, self.policy)
        self.executor = RoundExecutor(
            cfg, self.apply_fn, self.spec, self.backend,
            quantize=bool(getattr(self.policy, "quantize", False)),
            n_part=self.n_part, mesh=self.mesh,
            use_ef=cfg.caesar.use_error_feedback)
        self.store: Optional[ClientStateStore] = None

        # --- wire-boundary fault engine (DESIGN.md §11) -------------------
        if cfg.wire not in ("inproc", "loopback", "queue"):
            raise ValueError(f"unknown wire {cfg.wire!r} "
                             "(want inproc|loopback|queue)")
        if cfg.aggregation not in RB.AGGREGATIONS:
            raise ValueError(f"unknown aggregation {cfg.aggregation!r}; "
                             f"want one of {RB.AGGREGATIONS}")
        self._wire_on = cfg.wire != "inproc"
        if not self._wire_on and (cfg.faults.enabled()
                                  or cfg.aggregation != "mean"):
            raise ValueError(
                "fault injection and non-mean aggregation act on SERIALIZED "
                "payloads — set wire='loopback' (or 'queue')")
        if self._wire_on:
            if cfg.scheme != "caesar":
                raise ValueError("the wire engine currently supports "
                                 "scheme='caesar' only")
            if not cfg.ragged:
                raise ValueError("the wire engine requires ragged=True "
                                 "(it replays the tier-chunk stream)")
            if cfg.sharded:
                raise ValueError("the wire engine is single-mesh "
                                 "(set sharded=False)")
            self._byz_members = F.byzantine_members(
                cfg.faults, cfg.seed, cfg.n_clients)
            self._aggregator = RB.make_aggregator(
                cfg.aggregation, cohort=self.n_part,
                trim_frac=cfg.trim_frac, clip_norm=cfg.clip_norm,
                krum_f=cfg.krum_f, krum_m=cfg.krum_m)
        # uploads deferred from round t-1 under late_policy="defer":
        # list of (client id, WireUpload)
        self._deferred: list = []
        self._transport = None
        # one dict per simulated round (status/byz arrays + byte counts) —
        # the raw record fig11 and the resume test consume
        self.fault_log: list = []
        self._t_done = 0

        # --- trace-driven availability (DESIGN.md §12) -------------------
        self._avail_on = cfg.availability.enabled()
        if self._avail_on and cfg.sharded:
            raise ValueError(
                "diurnal availability is single-mesh (the stratified shard "
                "draw has no per-shard forced-wake story yet); set "
                "sharded=False")
        # static per-client home phases, drawn once — read-only after init,
        # so the prefetch worker shares them without synchronization
        self._avail_phases = (AV.client_phases(cfg.availability, cfg.seed,
                                               cfg.n_clients)
                              if self._avail_on else None)
        # one dict per round: eligibility counts + participant staleness —
        # the raw record fig11 reports against the download policy
        self.avail_log: list = []
        self._last_part = np.zeros(cfg.n_clients, np.int64)

        def evaluate(flat_params, x, y):
            logits = self.apply_fn(C.unflatten_vector(flat_params, self.spec),
                                   x)
            return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

        self._eval = jax.jit(evaluate)

    # planner-owned state, exposed for tests/benchmarks
    @property
    def caesar_state(self):
        return self.planner.caesar_state

    @property
    def grad_norms(self):
        return self.planner.grad_norms

    @property
    def splits(self):
        """Per-client sample-index views over the CSR split storage (compat
        shim for the old list-of-arrays attribute)."""
        return [self._split_idx[self._split_off[i]:self._split_off[i + 1]]
                for i in range(self.cfg.n_clients)]

    def _make_policy(self, name):
        if name == "fic":
            return BL.FIC(compress_down=not self.cfg.fic_up_only,
                          compress_up=not self.cfg.fic_down_only)
        if name == "cac":
            return BL.CAC(compress_down=not self.cfg.fic_up_only,
                          compress_up=not self.cfg.fic_down_only)
        return BL.POLICIES[name]()

    def _make_store(self) -> ClientStateStore:
        """Fresh per-run client-state pool. ``init_row`` holds the initial
        model pre-quantized to the storage dtype, so a pooled first-timer's
        activation write bit-matches the dense engine's broadcast init."""
        dt = self.executor.buf_dtype
        init_row = np.asarray(jnp.asarray(self.flat0, dt), np.float32)
        return ClientStateStore(
            self.cfg.n_clients, self.n_params, init_row,
            ef_width=self.executor.ef_width, dtype=dt,
            capacity=self.cfg.state_capacity, cohort=self.n_part,
            n_shards=self.n_dev, mesh=self.mesh,
            offload=self.cfg.state_offload,
            offload_dir=self.cfg.state_dir,
            volumes=self.volumes,
            measure_restore_error=self.cfg.measure_eviction_error)

    # ------------------------------------------------------------------
    # Host-side producer work (participant draw + plan + batch gather).
    # Every round owns a SeedSequence-derived RNG stream, so the pipelined
    # and synchronous drivers consume identical randomness — a shared
    # generator cannot be read out of lockstep from a worker thread.
    # ------------------------------------------------------------------

    def _round_rng(self, t: int) -> np.random.Generator:
        """Deterministic per-round stream: SeedSequence(seed, (2, t)).
        Spawn-key kinds are named in ``repro.core.rng`` — 0/1 belong to
        CapabilityModel, 2 is the round's sampling stream, 3 the executor's
        stochastic-rounding stream."""
        return RNG.stream(self.cfg.seed, RNG.KIND_SAMPLING, t)

    def _select_participants(self, rng: np.random.Generator, t: int
                             ) -> tuple[np.ndarray, int, int]:
        """Round t's cohort draw → (parts, n_eligible, n_forced).

        Availability off ("always"): the legacy uniform draw — byte-
        identical stream consumption, which the zero-fault bit-identity
        gate depends on; stratified per shard in sharded mode (each device
        must own its participants' pool rows). Diurnal: a uniform draw
        over the round's eligible set (fl/availability — pure numpy, safe
        on the prefetch worker); when fewer clients are online than the
        cohort needs, the server force-wakes the shortfall (the push-
        notification escape hatch real deployments use), drawn uniformly
        from the offline remainder — ``n_forced`` is the per-round count
        the avail_log reports."""
        n, d = self.cfg.n_clients, self.n_dev
        if not self._avail_on:
            return stratified_cohort(rng, n, self.n_part, d), n, 0
        mask = AV.eligible_mask(self.cfg.availability, self.cfg.seed, t, n,
                                self._avail_phases)
        el = np.flatnonzero(mask)
        if len(el) >= self.n_part:
            return rng.choice(el, self.n_part, replace=False), len(el), 0
        forced = rng.choice(np.flatnonzero(~mask), self.n_part - len(el),
                            replace=False)
        return (np.concatenate([el, forced]), len(el), len(forced))

    def _draw_indices(self, rng: np.random.Generator,
                      parts: np.ndarray) -> np.ndarray:
        """Cap-shaped batch-index draw [P, τ, b_max] — ALWAYS at the caps,
        whatever the plan says: the tier engine consumes a per-participant
        [:τ_tier, :b_tier] PREFIX of this draw, so the randomness stream is
        plan-independent (ragged and masked runs draw identically) and a
        participant's first b_i samples of iteration k are the same samples
        under either engine."""
        b_cap, tau_cap = self.cfg.caesar.b_max, self.cfg.caesar.tau
        off, pool = self._split_off, self._split_idx
        idx = np.empty((len(parts), tau_cap, b_cap), np.intp)
        for i, ci in enumerate(parts):
            idx[i] = rng.choice(pool[off[ci]:off[ci + 1]],
                                size=(tau_cap, b_cap), replace=True)
        return idx

    def _gather_cap(self, idx: np.ndarray, out):
        """Gather the cap-shaped training batches for ``idx`` into ``out``
        (a preallocated (xs, ys) pair — filled IN PLACE so the pipelined
        driver's two persistent buffer sets never mmap/munmap tens of MB
        mid-step, which would stall the XLA threads with TLB shootdowns)."""
        xtr, ytr = self.data.x_train, self.data.y_train
        xs, ys = out
        flat = idx.reshape(-1)
        np.take(xtr, flat, axis=0, out=xs.reshape((-1,) + xtr.shape[1:]))
        np.take(ytr, flat, axis=0, out=ys.reshape((-1,) + ytr.shape[1:]))
        return xs, ys

    def _prefetch_round(self, t: int, out=None):
        """Round t's cap-shaped host sampling: (participants, xs, ys).

        Pure numpy on data that is read-only after __init__. The batch
        *indices* need only the caps (b_max, τ) — plan-dependent
        per-participant (batch, τ_i) enter later as masks (`_batch_masks`)
        or tier prefixes. Kept as the cap-gather primitive for the masked
        engine, policy schemes, and external callers (bench_round's
        LegacyEngine drives it directly)."""
        rng = self._round_rng(t)
        parts, _n_el, _n_forced = self._select_participants(rng, t)
        idx = self._draw_indices(rng, parts)
        if out is None:
            out = self._alloc_batch_buffers(len(parts))
        xs, ys = self._gather_cap(idx, out)
        return parts, xs, ys

    def _alloc_batch_buffers(self, n_parts: int):
        """One cap-shaped (xs, ys) buffer set for `_prefetch_round`."""
        b_cap, tau_cap = self.cfg.caesar.b_max, self.cfg.caesar.tau
        xtr, ytr = self.data.x_train, self.data.y_train
        return (np.empty((n_parts, tau_cap, b_cap) + xtr.shape[1:],
                         xtr.dtype),
                np.empty((n_parts, tau_cap, b_cap) + ytr.shape[1:],
                         ytr.dtype))

    @staticmethod
    def _batch_masks(batch_sizes, taus, b_cap, tau_cap):
        """Per-participant (sample-weight [P,τ,b], iter-mask [P,τ]) masks
        realizing the planned batch sizes / local-iteration counts on the
        prefetched cap-shaped batches."""
        p = len(batch_sizes)
        ws = np.zeros((p, tau_cap, b_cap), np.float32)
        for i, b in enumerate(batch_sizes):
            ws[i, :, :int(b)] = 1.0
        ims = (np.arange(tau_cap)[None, :]
               < np.asarray(taus)[:, None]).astype(np.float32)
        return ws, ims

    # -- plan-shaped tier marshalling (DESIGN.md §8) -----------------------

    def _plan_tiers(self, batch: np.ndarray, taus: np.ndarray) -> list:
        """Quantize the plan to the (b, τ) lattice and group participants
        by tier. Deterministic processing order: tiers descending by
        (τ, b), participants within a tier in parts order (stable)."""
        ccfg = self.cfg.caesar
        bt, tt = BS.quantize_plan(batch, taus, ccfg.b_min, ccfg.b_max,
                                  ccfg.tau)
        groups = []
        for tau_t, b_t in sorted(set(zip(tt.tolist(), bt.tolist())),
                                 reverse=True):
            pos = np.flatnonzero((tt == tau_t) & (bt == b_t))
            groups.append((int(b_t), int(tau_t), pos))
        return groups

    def _tier_masks(self, batch, taus, pos, b_t, tau_t, g_pad):
        """Rung-padded (ws [g_pad,τ,b], ims [g_pad,τ]) realizing the exact
        planned (b_i, τ_i) inside the tier shape — identical semantics to
        `_batch_masks` at the cap, restricted to the tier prefix."""
        g = len(pos)
        ws = np.zeros((g_pad, tau_t, b_t), np.float32)
        ws[:g] = (np.arange(b_t)[None, None, :]
                  < np.asarray(batch)[pos, None, None])
        ims = np.zeros((g_pad, tau_t), np.float32)
        ims[:g] = (np.arange(tau_t)[None, :] < np.asarray(taus)[pos, None])
        return ws, ims

    def _ensure_flat_buffers(self, bufs: dict, x_rows: int):
        """Grow-on-demand flat sample pools the tier gather carves into —
        persistent per slot, so the steady state allocates nothing (the
        per-round total Σ g_pad·τ_t·b_t varies with tier occupancy)."""
        xtr, ytr = self.data.x_train, self.data.y_train
        cur = bufs.get("flat")
        if cur is None or cur[0].shape[0] < x_rows:
            bufs["flat"] = (np.empty((x_rows,) + xtr.shape[1:], xtr.dtype),
                            np.empty((x_rows,) + ytr.shape[1:], ytr.dtype))
        return bufs["flat"]

    def _tiers_from_idx(self, idx: np.ndarray, batch, taus,
                        bufs: dict) -> list:
        """Tier-shaped batch gather (the pipelined worker's path): for each
        tier, gather ONLY the [:τ_t, :b_t] prefix of the cap-shaped index
        draw — host sampling bytes shrink by the plan-shaped work factor."""
        groups = self._plan_tiers(batch, taus)
        layouts = [self.executor.tier_layout(len(pos))
                   for _, _, pos in groups]
        total = sum(gl[0] * tau_t * b_t
                    for (b_t, tau_t, _), gl in zip(groups, layouts))
        xflat, yflat = self._ensure_flat_buffers(bufs, total)
        xtr, ytr = self.data.x_train, self.data.y_train
        feat = xtr.shape[1:]
        tiers, off = [], 0
        for (b_t, tau_t, pos), (g_pad, slices) in zip(groups, layouts):
            rows = g_pad * tau_t * b_t
            xv = xflat[off:off + rows]
            yv = yflat[off:off + rows]
            off += rows
            sel = idx[pos, :tau_t, :b_t].reshape(-1)
            np.take(xtr, sel, axis=0, out=xv[:sel.size])
            np.take(ytr, sel, axis=0, out=yv[:sel.size])
            if rows > sel.size:          # zero the rung padding
                xv[sel.size:] = 0
                yv[sel.size:] = 0
            ws, ims = self._tier_masks(batch, taus, pos, b_t, tau_t, g_pad)
            tiers.append(TierGroup(
                b=b_t, tau=tau_t, pos=pos, g_pad=g_pad, slices=slices,
                xs=xv.reshape((g_pad, tau_t, b_t) + feat),
                ys=yv.reshape((g_pad, tau_t, b_t)), ws=ws, ims=ims))
        return tiers

    def _tiers_from_cap(self, xs: np.ndarray, ys: np.ndarray, batch,
                        taus) -> list:
        """Tier groups sliced out of an already cap-gathered batch (the
        policy-scheme path, where the plan needs execution feedback and is
        only known on the main thread after the worker gathered)."""
        groups = self._plan_tiers(batch, taus)
        tiers = []
        for b_t, tau_t, pos in groups:
            g = len(pos)
            g_pad, slices = self.executor.tier_layout(g)
            xs_t = np.zeros((g_pad, tau_t, b_t) + xs.shape[3:], xs.dtype)
            xs_t[:g] = xs[pos, :tau_t, :b_t]
            ys_t = np.zeros((g_pad, tau_t, b_t), ys.dtype)
            ys_t[:g] = ys[pos, :tau_t, :b_t]
            ws, ims = self._tier_masks(batch, taus, pos, b_t, tau_t, g_pad)
            tiers.append(TierGroup(b=b_t, tau=tau_t, pos=pos, g_pad=g_pad,
                                   slices=slices, xs=xs_t, ys=ys_t, ws=ws,
                                   ims=ims))
        return tiers

    def _plan_faults(self, t: int, parts: np.ndarray,
                     plan: tuple, mu, bw_d, bw_u) -> Optional[F.FaultPlan]:
        """Round t's fault draw. Pure numpy (it runs on the prefetch
        worker — REP003 keeps device ops off the producer thread, which is
        why the deadline uses ``faults.round_times_np``, the f64 twin of
        ``core.batchsize.round_times``). None when the wire engine is off."""
        if not self._wire_on:
            return None
        cfg = self.cfg
        times = None
        if cfg.faults.straggler_deadline > 0.0:
            theta_d, theta_u, batch, taus = plan
            times = F.round_times_np(
                np.asarray(theta_d, np.float64),
                np.asarray(theta_u, np.float64),
                float(self.model_bits), bw_d[parts], bw_u[parts],
                np.asarray(taus, np.float64),
                np.asarray(batch, np.float64), mu[parts])
        return F.plan_faults(cfg.faults, cfg.seed, t, parts, times,
                             self._byz_members)

    def _prefetch_pkg(self, t: int, bufs: dict) -> RoundPkg:
        """The full producer step for round t (worker thread when
        pipelined): draw → capability snapshot → [Caesar: plan + state
        advance] → batch gather (tier-shaped when the plan is known,
        cap-shaped otherwise). Never touches the state store."""
        with span("prefetch", round=t):
            rng = self._round_rng(t)
            parts, n_el, n_forced = self._select_participants(rng, t)
            idx = self._draw_indices(rng, parts)
            mu, bw_d, bw_u = self.cap.snapshot(t)
            if self.planner.is_caesar and self.cfg.ragged:
                # planning inside the producer is what makes the TIER-shaped
                # gather possible; without that payoff (masked mode) the
                # plan stays on the main thread — its (tiny) jitted math
                # would only contend with the in-flight device step
                with span("prefetch.plan", round=t):
                    plan = self.planner.plan(t, parts, mu, bw_d, bw_u)
                    fplan = self._plan_faults(t, parts, plan, mu, bw_d, bw_u)
                    # failed rounds never advance their clients'
                    # participation record: a dropped client's next round
                    # must resync exactly as if it had not participated
                    # (its pool row rolls back too)
                    self.planner.advance(
                        t, parts if fplan is None else parts[fplan.record])
                with span("prefetch.gather", round=t):
                    tiers = self._tiers_from_idx(idx, plan[2], plan[3], bufs)
                return RoundPkg(parts, mu, bw_d, bw_u, plan=plan,
                                tiers=tiers, fplan=fplan, n_eligible=n_el,
                                n_forced=n_forced)
            if "cap" not in bufs:
                bufs["cap"] = self._alloc_batch_buffers(self.n_part)
            with span("prefetch.gather", round=t):
                xs, ys = self._gather_cap(idx, bufs["cap"])
            return RoundPkg(parts, mu, bw_d, bw_u, xs=xs, ys=ys,
                            n_eligible=n_el, n_forced=n_forced)

    # ------------------------------------------------------------------
    # The wire-boundary round (DESIGN.md §11): deferred tier-chunk step →
    # per-client serialize (+ attack/corrupt) → transport → server decode
    # + robust aggregate. Replays the exact chunk stream the in-process
    # engine folds, so zero faults + mean + f32 is bit-identical (CI-gated).
    # ------------------------------------------------------------------

    def _wire_round(self, global_f, store, pkg: RoundPkg, tiers, lr,
                    td32, tu32, t: int):
        cfg = self.cfg
        fp = pkg.fplan
        parts = pkg.parts
        chunks, db_o, ub_o, gn_o = self.executor.step_ragged_deferred(
            global_f, store, parts, tiers, lr, td32, tu32, t=t,
            wmask=fp.adopt)

        # -- client side: serialize each surviving upload onto the wire.
        # Two passes over the SAME chunk-stream order the old single loop
        # walked (send order is part of the bit-identity contract): pass 1
        # collects each survivor's sparse honest upload, pass 2 swaps in
        # the adversarial payload and transmits. The split exists for the
        # colluding ALIE attack, whose shared vector needs the round's
        # honest statistics before any attacker can transmit (the standard
        # ALIE omniscience assumption).
        tr = self._transport
        wire_bytes = 0
        resent = np.zeros(len(parts), bool)
        sent = []        # pos (parts order) in send order
        retained = {}    # pos -> clean payload, for the retry-once path
        rows = []        # (pos, idx [k], vals [k]) in chunk-stream order
        with span("wire.collect", round=t):
            for pos_c, slots, c, ups in chunks:
                ups_np = np.asarray(ups)
                for row_i, pos in zip(slots, pos_c):
                    pos = int(pos)
                    if fp.status[pos] == F.DROP:
                        continue
                    row = ups_np[row_i]
                    idx = np.flatnonzero(row)
                    rows.append((pos, idx, row[idx]))
        with span("wire.encode", round=t):
            alie = None
            if cfg.faults.attack == "alie" and bool(fp.byz.any()):
                hsum = np.zeros(self.n_params, np.float64)
                hsq = np.zeros(self.n_params, np.float64)
                hn, hks, hnorms = 0, [], []
                for pos, idx, vals in rows:
                    if fp.byz[pos]:
                        continue
                    v64 = vals.astype(np.float64)
                    hsum[idx] += v64
                    hsq[idx] += v64 * v64
                    hn += 1
                    hks.append(len(idx))
                    hnorms.append(float(np.linalg.norm(v64)))
                if hn:
                    alie = F.alie_payload(cfg.faults, hsum, hsq, hn,
                                          int(np.median(hks)),
                                          float(np.median(hnorms)))
            for pos, idx, vals in rows:
                if fp.byz[pos]:
                    idx, vals = F.attack_payload(
                        cfg.faults, cfg.seed, t, int(parts[pos]), idx, vals,
                        self.n_params, alie=alie)
                payload = W.encode_upload(
                    idx, vals, client=int(parts[pos]), round_=t,
                    n_params=self.n_params,
                    value_dtype=cfg.wire_value_dtype)
                retained[pos] = payload
                wire_bytes += len(payload)
                if fp.corrupt_first[pos]:
                    payload = F.flip_bit(payload, cfg.seed, t,
                                         int(parts[pos]), salt=0)
                tr.send(payload)
                sent.append(pos)
            payloads = (tr.drain(len(sent)) if cfg.wire == "queue"
                        else tr.drain())

        # -- server side: decode + CRC check, retry-once, deadline sort --
        accepted = []        # (pos, WireUpload) folded THIS round
        deferred_next = []   # (client, WireUpload) arriving next round
        n_crc_drop = 0
        with span("wire.decode", round=t):
            for pos, payload in zip(sent, payloads):
                try:
                    u = W.decode_upload(payload)
                except W.WireCRCError:
                    # retry-once: the client retransmits its retained
                    # payload (priced as real traffic); a corrupted retry
                    # drops it
                    p2 = retained[pos]
                    wire_bytes += len(p2)
                    resent[pos] = True
                    if fp.status[pos] == F.CORRUPT_DROP:
                        p2 = F.flip_bit(p2, cfg.seed, t, int(parts[pos]),
                                        salt=1)
                    try:
                        u = W.decode_upload(p2)
                    except W.WireCRCError:
                        n_crc_drop += 1
                        continue
                if fp.status[pos] == F.LATE:
                    if cfg.faults.late_policy == "defer":
                        deferred_next.append((int(parts[pos]), u))
                    continue
                accepted.append((pos, u))
        defer_in = self._deferred
        self._deferred = deferred_next

        # -- robust aggregate: replay the chunk stream + late arrivals --
        with span("agg.host", round=t):
            agg = self._aggregator
            if agg.needs_norms:
                norms = np.asarray(
                    [float(np.linalg.norm(u.values)) for _, u in accepted]
                    + [float(np.linalg.norm(u.values)) for _, u in defer_in])
                sc = agg.scales(norms)
                w_of = dict(zip([pos for pos, _ in accepted], sc.tolist()))
                w_defer = sc[len(accepted):].tolist()
            else:
                w_of = {pos: 1.0 for pos, _ in accepted}
                w_defer = [1.0] * len(defer_in)
            by_pos = dict(accepted)
            carry = agg.init(self.n_params)
            cnt = 0
            for pos_c, slots, c, _ups in chunks:
                dense = np.zeros((c, self.n_params), np.float32)
                w = np.zeros(c, np.float32)
                for row_i, pos in zip(slots, pos_c):
                    u = by_pos.get(int(pos))
                    if u is None:
                        continue
                    dense[row_i, u.indices] = u.values
                    w[row_i] = w_of[int(pos)]
                    cnt += 1
                carry = agg.update(carry, dense, w)
            if defer_in:
                # deferred arrivals fold after the live chunks, rung-padded
                # so the jit cache sees power-of-two shapes only
                d = len(defer_in)
                d_pad = 1 << (d - 1).bit_length()
                dense = np.zeros((d_pad, self.n_params), np.float32)
                w = np.zeros(d_pad, np.float32)
                for i, (_cl, u) in enumerate(defer_in):
                    dense[i, u.indices] = u.values
                    w[i] = w_defer[i]
                carry = agg.update(carry, dense, w)
                cnt += d
            new_global = agg.finalize(global_f, carry, cnt)

        self.fault_log.append({
            "round": t, "parts": parts.copy(),
            "status": fp.status.copy(), "byz": fp.byz.copy(),
            "corrupt_first": fp.corrupt_first.copy(),
            "n_aggregated": len(accepted), "n_deferred_in": len(defer_in),
            "n_deferred_out": len(deferred_next),
            "n_crc_dropped": n_crc_drop, "wire_bytes": wire_bytes})
        # modeled upload traffic: only bytes that hit the wire count, and
        # a CRC retry pays twice
        up_eff = (ub_o * fp.uploads_sent().astype(np.float32)
                  * (1.0 + resent.astype(np.float32)))
        return new_global, db_o, up_eff, gn_o, wire_bytes

    def _init_global(self):
        """Fresh [n_params] f32 global vector — the step donates it, so
        `flat0` itself must stay intact. The client-local rows live in the
        ClientStateStore pool (`_make_store`), not here."""
        if self.mesh is None:
            return jnp.array(self.flat0, copy=True)
        return MESH.host_local_array(self.mesh, P(),
                                     np.asarray(self.flat0).copy())

    # ------------------------------------------------------------------
    def run(self, log: Callable[[str], None] = lambda s: None,
            start_round: int = 1) -> History:
        """Simulate rounds [start_round, cfg.rounds]. ``start_round > 1``
        resumes a checkpoint previously installed via `load_state_dict`
        (planner/store/global/accounting state all restored); because every
        per-round draw — sampling, stochastic rounding AND the fault
        schedule — is keyed by (seed, kind, t), the resumed tail replays
        the identical rounds the uninterrupted run would have simulated."""
        cfg = self.cfg
        ccfg = cfg.caesar
        b_max, tau = ccfg.b_max, ccfg.tau
        q_bits = float(self.model_bits)
        hist = History()
        if start_round > 1:
            rs = getattr(self, "_resume", None)
            if rs is None or rs["t_done"] != start_round - 1:
                raise ValueError(
                    f"start_round={start_round} needs a checkpoint of "
                    f"{start_round - 1} completed rounds loaded via "
                    "load_state_dict")
            global_f = jnp.asarray(np.asarray(rs["global_flat"]))
            store = self.store
            cum_time, cum_bits, waiting_sum = rs["acct"]
            wire_bits_cum = rs["wire_bits"]
        else:
            global_f = self._init_global()
            store = self.store = self._make_store()
            cum_time, cum_bits, waiting_sum = 0.0, 0.0, 0.0
            wire_bits_cum = 0.0
            self._deferred = []
            self.fault_log = []
            self.avail_log = []
            self._last_part = np.zeros(cfg.n_clients, np.int64)
        self._transport = (W.make_transport(cfg.wire) if self._wire_on
                           else None)
        # double-buffered producer: one worker prefetches round t+1's
        # package (participants, plan, tier- or cap-shaped batches — pure
        # numpy + tiny jitted plan math) into the OFF buffer slot while the
        # device runs round t from the other — two persistent slots, filled
        # in place, so steady state allocates nothing
        pool = (ThreadPoolExecutor(max_workers=1) if cfg.pipelined
                else None)
        n_bufs = 2 if pool else 1
        bufs = [dict() for _ in range(n_bufs)]

        def prefetch(t):
            return self._prefetch_pkg(t, bufs[t % n_bufs])

        try:
            pending = pool.submit(prefetch, start_round) if pool else None
            for t in range(start_round, cfg.rounds + 1):
                with span("round", round=t):
                    wall0 = time.perf_counter()
                    if pool:
                        with span("round.wait_prefetch", round=t):
                            pkg = pending.result()
                        if t < cfg.rounds:
                            pending = pool.submit(prefetch, t + 1)
                    else:
                        pkg = prefetch(t)
                    with span("round.setup", round=t):
                        parts = pkg.parts
                        mu, bw_d, bw_u = pkg.mu, pkg.bw_d, pkg.bw_u
                        # participant staleness at draw time (δ = t − last
                        # recorded participation; δ = t for first-timers) —
                        # the distribution the download policy keys
                        # compression off, logged per round alongside the
                        # availability counts. MAIN thread only:
                        # `_last_part` must advance in round order.
                        stale = t - self._last_part[parts]
                        self.avail_log.append({
                            "round": t, "n_eligible": int(pkg.n_eligible),
                            "n_forced": int(pkg.n_forced),
                            "staleness": AV.staleness_stats(stale)})
                        rec = (parts if pkg.fplan is None
                               else parts[pkg.fplan.record])
                        self._last_part[rec] = t
                        lr = jnp.float32(SGD.lr_at(cfg.sgd,
                                                   jnp.float32(t - 1)))

                        if pkg.plan is not None:
                            theta_d, theta_u, batch, taus = pkg.plan
                        else:
                            with span("round.plan", round=t):
                                theta_d, theta_u, batch, taus = \
                                    self.planner.plan(t, parts, mu, bw_d,
                                                      bw_u)
                                # participation records advance right
                                # after planning (masked caesar; the worker
                                # never touches the planner on this path,
                                # so main-thread ordering is the only
                                # ordering)
                                self.planner.advance(t, parts)
                        td32 = np.asarray(theta_d, np.float32)
                        tu32 = np.asarray(theta_u, np.float32)
                    wire_bytes = 0
                    if cfg.ragged:
                        tiers = (pkg.tiers if pkg.tiers is not None else
                                 self._tiers_from_cap(pkg.xs, pkg.ys, batch,
                                                      taus))
                        if self._wire_on:
                            (global_f, down_bits, up_bits, gnorms,
                             wire_bytes) = self._wire_round(
                                global_f, store, pkg, tiers, lr, td32, tu32,
                                t)
                        else:
                            (global_f, down_bits, up_bits,
                             gnorms) = self.executor.step_ragged(
                                global_f, store, parts, tiers, lr, td32,
                                tu32, t=t)
                    else:
                        ws, ims = self._batch_masks(batch, taus, b_max, tau)
                        (global_f, down_bits, up_bits,
                         gnorms) = self.executor.step(
                            global_f, store, parts, pkg.xs, pkg.ys,
                            ws, ims, lr, td32, tu32, t=t)
                    self.planner.observe(t, parts, gnorms)

                    with span("round.account", round=t):
                        # traffic: actual hybrid/top-k payload bits on the
                        # wire. THE documented per-round sync point:
                        # blocking on the step outputs here is what makes
                        # wall_per_round honest
                        down_b = np.asarray(down_bits, np.float64)  # repro: noqa=REP006
                        up_b = np.asarray(up_bits, np.float64)  # repro: noqa=REP006
                        cum_bits += float(down_b.sum() + up_b.sum())
                        # time + barrier waiting: the Eq.-7 θ·Q/β model —
                        # the SAME model optimize_batch_sizes equalizes
                        # (core/batchsize.py), evaluated at the PLANNED
                        # (b_i, τ_i) — tier quantization is an
                        # executor-shape concern, invisible to simulated
                        # time
                        times = np.asarray(BS.round_times(
                            np.asarray(theta_d, np.float64),
                            np.asarray(theta_u, np.float64), q_bits,
                            bw_d[parts], bw_u[parts],
                            np.asarray(taus, np.float64),
                            np.asarray(batch, np.float64), mu[parts]))
                        # under the wire engine a straggler deadline CLOSES
                        # the round early (late uploads discarded or
                        # deferred); with no deadline (inf) this is exactly
                        # the legacy barrier
                        close = float(times.max())
                        if pkg.fplan is not None:
                            close = min(close, float(pkg.fplan.deadline))
                        cum_time += close
                        waiting = float(np.mean(np.maximum(close - times,
                                                           0.0)))
                    waiting_sum += waiting
                    wire_bits_cum += wire_bytes * 8.0
                    self._t_done = t
                    hist.waiting_per_round.append(waiting)
                    # the np.asarray conversions above synced on the step
                    # outputs, so this is an honest per-round host
                    # wall-clock
                    hist.wall_per_round.append(time.perf_counter() - wall0)

                    if t % cfg.eval_every == 0 or t == cfg.rounds:
                        ne = min(cfg.eval_samples, len(self.data.y_test))
                        # eval boundary, cadence-limited by cfg.eval_every
                        with span("round.eval", round=t):
                            acc = float(self._eval(  # repro: noqa=REP006
                                global_f, jnp.asarray(self.data.x_test[:ne]),
                                jnp.asarray(self.data.y_test[:ne])))
                            hist.rounds.append(t)
                            hist.sim_time.append(cum_time)
                            hist.traffic_bits.append(cum_bits)
                            hist.accuracy.append(acc)
                            hist.waiting.append(waiting_sum / t)
                            if self._wire_on:
                                hist.wire_bits.append(wire_bits_cum)
                            log(f"[{cfg.scheme}/{cfg.dataset}] round {t:4d} "
                                f"acc={acc:.4f} time={cum_time:,.0f}s "
                                f"traffic={cum_bits/8e9:.3f}GB "
                                f"wait={waiting_sum / t:.1f}s")
                        if (cfg.target_accuracy is not None
                                and acc >= cfg.target_accuracy):
                            break
        finally:
            if pool:
                pool.shutdown(wait=False, cancel_futures=True)
            if self._transport is not None:
                self._transport.close()
                self._transport = None
        self.global_flat = global_f          # expose final flat model
        self.ef_flat = store.ef_pool         # [capacity, ef_width] residuals
        self._acct = (cum_time, cum_bits, waiting_sum)
        self._wire_bits_cum = wire_bits_cum
        return hist

    # ------------------------------------------------------------------
    # Checkpoint / resume (DESIGN.md §11). Everything a resumed tail needs
    # to replay bit-identically: the global model, the client-state pool,
    # the planner's participation record + grad norms, the accounting
    # counters, and any uploads deferred across the checkpoint boundary.
    # The fault schedule itself needs NO state — it is a pure function of
    # (seed, KIND_FAULTS, t), so the resumed run redraws it identically.
    # ------------------------------------------------------------------

    def state_dict(self) -> dict:
        """Portable (numpy-only) checkpoint after `run` simulated
        ``self._t_done`` rounds. Feed to a FRESH Simulator of the same
        config via `load_state_dict`, then `run(start_round=t_done + 1)`."""
        leaves, _ = jax.tree_util.tree_flatten(self.planner.caesar_state)
        return {
            "t_done": int(self._t_done),
            "global_flat": np.asarray(self.global_flat).copy(),
            "store": self.store.state_dict(),
            "caesar_leaves": [np.asarray(x).copy() for x in leaves],
            "grad_norms": self.planner.grad_norms.copy(),
            "acct": tuple(getattr(self, "_acct", (0.0, 0.0, 0.0))),
            "wire_bits": float(getattr(self, "_wire_bits_cum", 0.0)),
            "deferred": [(int(cl), int(u.round), u.indices.copy(),
                          u.values.copy()) for cl, u in self._deferred],
            "fault_log": [dict(e) for e in self.fault_log],
            "last_part": self._last_part.copy(),
            "avail_log": [dict(e) for e in self.avail_log],
        }

    def load_state_dict(self, d: dict) -> None:
        """Install a `state_dict` checkpoint (rebuilds the store via
        `_make_store`, restores the planner pytree against this config's
        treedef) and arm `run(start_round=...)` to continue it."""
        _, treedef = jax.tree_util.tree_flatten(self.planner.caesar_state)
        self.planner.caesar_state = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(np.asarray(x)) for x in d["caesar_leaves"]])
        self.planner.grad_norms = np.asarray(d["grad_norms"]).copy()
        store = self._make_store()
        store.load_state_dict(d["store"])
        self.store = store
        self.global_flat = jnp.asarray(np.asarray(d["global_flat"]))
        self._deferred = [
            (cl, W.WireUpload(client=cl, round=r, n_params=self.n_params,
                              indices=np.asarray(ix, np.int32),
                              values=np.asarray(v, np.float32)))
            for cl, r, ix, v in d["deferred"]]
        self.fault_log = [dict(e) for e in d["fault_log"]]
        self._last_part = np.asarray(
            d.get("last_part", np.zeros(self.cfg.n_clients, np.int64))
        ).copy()
        self.avail_log = [dict(e) for e in d.get("avail_log", [])]
        self._t_done = int(d["t_done"])
        self._resume = {"t_done": int(d["t_done"]),
                        "global_flat": np.asarray(d["global_flat"]).copy(),
                        "acct": tuple(d["acct"]),
                        "wire_bits": float(d["wire_bits"])}

    def reset(self):
        """Reset round/planner state so `run` can be repeated on the SAME
        simulator: the replay consumes identical seed streams against warm
        jit caches (`run` builds a fresh state pool each call). The ragged
        engine compiles tier shapes lazily as rounds first occupy them, so
        a cold run folds shape compiles into mid-run walls; a reset+rerun
        measures the steady state (every executor cache intact, no
        model/plan state carried over)."""
        self.planner = RoundPlanner(self.cfg, self.volumes, self.label_dist,
                                    self.model_bits, self.policy)

    # ------------------------------------------------------------------
    def global_params(self) -> Any:
        """Final global model as a pytree (unflatten only at the boundary)."""
        flat = getattr(self, "global_flat", self.flat0)
        return C.unflatten_vector(flat, self.spec)
