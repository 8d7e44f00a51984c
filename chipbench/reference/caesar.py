"""Plain reference of the first rounds of a Caesar federation.

Written from the paper (Caesar, arXiv:2412.19989, Algorithm 1 and Eqs.
3-9) and the cell's stated settings, in straightforward numpy and
``jax.numpy``; it imports nothing of the system under test and takes
nothing it made. Given the seed it draws, round by round:

* the cohort and each participant's batch indices, from the round's own
  ``SeedSequence(seed, spawn_key=(2, t))`` stream;
* each device's per-sample latency and link bandwidths (a persistent
  log-uniform hardware tier, a work mode redrawn every 20 rounds and a
  per-round WiFi draw of 1-30 Mb/s);
* the plan: the download ratio from the staleness of each participant's
  local model, grouped into quantile clusters (Eq. 3); the upload ratio
  from the rank of its importance (Eqs. 4-6); its batch size from the
  round-time model (Eqs. 7-9);

and then runs every participant: the global model compressed at its
download ratio (the smallest magnitudes sent as 1-bit signs with their
mean and max, the threshold being the lower edge of the 256-bin magnitude
histogram's bin that reaches the ratio) and recovered against its stale
local model (Fig. 3); tau local SGD steps at its batch size; the update
top-k sparsified at its upload ratio; and the mean of the sparse updates
applied to the global model. Traffic is the payload of each message.

``mode`` sets the arithmetic of local training: ``"reference"`` is the
precision the cells state, float32 weights with every convolution and
matmul at JAX's default precision (on a TPU one MXU pass: inputs rounded
to bfloat16, products accumulated in float32); ``"control"`` rounds every
convolution and matmul input to float8 (e4m3) first, the step below the
bfloat16 inputs the cells state, which a faster implementation would be
tempted to take. ``fault="half_cohort"`` leaves the second half of each
round's participants out of the mean; ``fault="no_down_bits"`` leaves the
download payloads out of the traffic count.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

N_BINS = 256
FULL_BITS, SIGN_BITS, STAT_BITS, INDEX_BITS = 32, 1, 64, 32
MU_RANGE = (0.002, 0.2)          # s per sample
BW_RANGE = (1e6, 30e6)           # bit/s
MODE_PERIOD = 20                 # rounds between work-mode redraws
KIND_CAP_EPOCH, KIND_CAP_ROUND, KIND_SAMPLING, KIND_CAP_TIER = 0, 1, 2, 4
KIND_FAULTS = 7


def _rng(seed, *key):
    return np.random.default_rng(np.random.SeedSequence(seed,
                                                        spawn_key=key))


@dataclasses.dataclass(frozen=True)
class Settings:
    """The cell's federation settings the reference needs."""
    n_clients: int
    n_part: int
    tau: int
    b_max: int
    b_min: int
    theta_d_max: float
    theta_u_min: float
    theta_u_max: float
    lam: float
    n_clusters: int
    lr: float
    lr_decay: float
    # the wire cell: dropouts, sign-flipping attackers, trimmed mean
    dropout_rate: float = 0.0
    byzantine_frac: float = 0.0
    attack_scale: float = 10.0
    aggregation: str = "mean"
    trim_frac: float = 0.1


# -- devices -----------------------------------------------------------------

class Devices:
    def __init__(self, n: int, seed: int):
        rng = _rng(seed, KIND_CAP_TIER)
        self.tier = np.exp(rng.uniform(np.log(MU_RANGE[0]),
                                       np.log(MU_RANGE[1]), n))
        self.bw_tier = rng.uniform(0.3, 1.0, n)
        self.n, self.seed = n, seed

    def snapshot(self, t: int):
        mode = np.exp(_rng(self.seed, KIND_CAP_EPOCH, t // MODE_PERIOD)
                      .normal(0.0, 0.5, self.n))
        mu = np.clip(self.tier * mode, *MU_RANGE)
        r = _rng(self.seed, KIND_CAP_ROUND, t)
        lo, hi = BW_RANGE
        bw_d = np.clip(self.bw_tier * r.uniform(lo, hi, self.n), lo, hi)
        bw_u = np.clip(self.bw_tier * r.uniform(lo, hi, self.n), lo, hi)
        return mu, bw_d, bw_u


# -- planning (float32, as the paper's PS would run it) ----------------------

def upload_ratios(volumes, label_dist, s: Settings):
    """Eqs. 4-6: importance C_i, ranked descending, to theta_u."""
    vol = jnp.asarray(volumes, jnp.float32)
    ld = jnp.clip(jnp.asarray(label_dist, jnp.float32), 1e-12, 1.0)
    kl = jnp.sum(ld * jnp.log(ld * ld.shape[-1]), axis=-1)
    c = s.lam * vol / jnp.maximum(jnp.max(vol), 1.0) \
        + (1.0 - s.lam) * jnp.exp(-kl)
    order = jnp.argsort(-c, stable=True)
    rank = jnp.zeros(c.shape[0], jnp.int32).at[order].set(
        jnp.arange(c.shape[0], dtype=jnp.int32))
    return (s.theta_u_min + (s.theta_u_max - s.theta_u_min) / c.shape[0]
            * rank.astype(jnp.float32))


def _ratio(mean_delta, t, theta_max):
    return jnp.clip(1.0 - mean_delta / t, 0.0, 1.0) * theta_max


def download_ratios(last, t: int, mask, s: Settings):
    """Eq. 3 over staleness quantile clusters of the participants; a
    first-time participant (delta = t) always gets the full model."""
    delta = (t - last).astype(jnp.int32)
    d = delta.astype(jnp.float32)
    m = mask.astype(jnp.float32)
    n_sel = jnp.maximum(jnp.sum(m), 1.0)
    d_sorted = jnp.sort(jnp.where(m > 0, d, jnp.inf))
    qs = jnp.linspace(0.0, 1.0, s.n_clusters + 1)[1:-1]
    pos = jnp.clip((qs * (n_sel - 1.0)).astype(jnp.int32), 0, d.shape[0] - 1)
    cid = jnp.searchsorted(d_sorted[pos], d).astype(jnp.int32)
    sums = jnp.zeros(s.n_clusters).at[cid].add(d * m)
    cnts = jnp.zeros(s.n_clusters).at[cid].add(m)
    tf = jnp.maximum(t, 1).astype(jnp.float32)
    per = _ratio(sums / jnp.maximum(cnts, 1.0), tf, s.theta_d_max)
    return jnp.where(delta >= t, 0.0, per[cid])


def batch_sizes(theta_d, theta_u, q_bits, bw_d, bw_u, mu, mask,
                s: Settings):
    """Eqs. 7-9: the fastest participant takes b_max; everyone else the
    largest batch that finishes no later."""
    comm = theta_d * (q_bits / bw_d) + theta_u * (q_bits / bw_u)
    full = comm + s.tau * float(s.b_max) * mu
    leader = jnp.argmin(jnp.where(mask, full, jnp.inf))
    b = jnp.floor((full[leader] - comm) / (s.tau * mu))
    b = jnp.clip(b, s.b_min, s.b_max).astype(jnp.int32)
    return b.at[leader].set(s.b_max)


@functools.partial(jax.jit, static_argnames=("s",))
def _plan_jit(last, t, mask, tu_all, bw_d, bw_u, mu, q_bits, s):
    td = download_ratios(last, t, mask, s)
    return td, batch_sizes(td, tu_all, q_bits, bw_d, bw_u, mu, mask, s)


def tier_rungs(lo: int, hi: int) -> list:
    r, out = hi, set()
    while r > lo:
        out.add(r)
        r = (r + 1) // 2
    out.add(lo)
    return sorted(out)


def tier_of(b: int, s: Settings) -> tuple:
    """(b, tau) rounded up to the halving ladders the executor pads to."""
    br = tier_rungs(s.b_min, s.b_max)
    tr = tier_rungs(1, s.tau)
    return (next(r for r in br if r >= b), next(r for r in tr if r >= s.tau))


# -- compression (Fig. 3 and top-k) ------------------------------------------

def threshold(x, ratio):
    """Lower edge of the first 256-bin magnitude bin whose cdf reaches
    ratio * n (so ratio 0 gives 0 and compresses nothing)."""
    mag = jnp.abs(x)
    mx = jnp.max(mag)
    idx = jnp.clip((mag * (N_BINS / jnp.maximum(mx, 1e-30))).astype(
        jnp.int32), 0, N_BINS - 1)
    cdf = jnp.cumsum(jnp.zeros(N_BINS, jnp.int32).at[idx].add(1)).astype(
        jnp.float32)
    b = jnp.searchsorted(cdf, jnp.clip(ratio, 0.0, 1.0) * cdf[-1],
                         side="left")
    return b.astype(jnp.float32) * (jnp.maximum(mx, 1e-30) / N_BINS)


def download(global_f, local, theta_d):
    """Compress the global model at theta_d and recover it against the
    participant's stale local model. Returns (model, payload bits)."""
    thr = threshold(global_f, theta_d)
    small = jnp.abs(global_f) < thr
    sgn = jnp.where(small, jnp.sign(global_f), 0.0)
    cnt = jnp.sum(small)
    mean_abs = jnp.sum(jnp.where(small, jnp.abs(global_f), 0.0)) \
        / jnp.maximum(cnt, 1)
    max_abs = jnp.max(jnp.where(small, jnp.abs(global_f), 0.0))
    # a slot sent as a sign keeps the local value unless that contradicts
    # the sign or exceeds the max, when it becomes sign * mean; a zero
    # sign marks a slot sent in full
    bad = (jnp.sign(local) * sgn < 0) | (jnp.abs(local) > max_abs)
    approx = jnp.where(bad, sgn * mean_abs, local)
    model = jnp.where(sgn != 0, approx, global_f)
    n = global_f.shape[0]
    bits = (n - cnt).astype(jnp.float32) * FULL_BITS \
        + cnt.astype(jnp.float32) * SIGN_BITS + STAT_BITS
    return model, bits


def upload(delta, theta_u):
    thr = threshold(delta, theta_u)
    up = jnp.where(jnp.abs(delta) < thr, 0.0, delta)
    keep = jnp.sum(jnp.abs(delta) >= thr).astype(jnp.float32)
    return up, keep * (FULL_BITS + INDEX_BITS)


def aggregate(rows: list, s: Settings, n: int):
    """The server's update from the uploads it received: their mean, or
    per coordinate the mean of what is left after dropping the trim_k
    largest and trim_k smallest values (uploads are dense, zero off their
    support), trim_k = round(trim_frac * cohort)."""
    if not rows:
        return jnp.zeros(n, jnp.float32)
    m = jnp.stack(rows)
    if s.aggregation == "mean":
        return jnp.sum(m, axis=0) / float(len(rows))
    if s.aggregation != "trimmed_mean":
        raise ValueError(f"no reference for aggregation {s.aggregation!r}")
    k = max(1, int(round(s.trim_frac * s.n_part)))
    srt = jnp.sort(m, axis=0)
    return jnp.sum(srt[k:len(rows) - k], axis=0) / float(len(rows) - 2 * k)


# -- the rounds --------------------------------------------------------------

class Planner:
    """Round t's cohort, batch indices and plan, from the seed alone."""

    def __init__(self, s: Settings, seed: int, splits, label_dist, volumes,
                 q_bits: float):
        self.s, self.seed, self.splits, self.q_bits = s, seed, splits, q_bits
        self.devices = Devices(s.n_clients, seed)
        self.theta_u = upload_ratios(volumes, label_dist, s)
        self.last = np.zeros(s.n_clients, np.int32)
        self.byz = np.zeros(s.n_clients, bool)
        k = int(round(s.byzantine_frac * s.n_clients))
        if k:
            self.byz[_rng(seed, KIND_FAULTS, 0).choice(
                s.n_clients, size=k, replace=False)] = True

    def plan(self, t: int):
        """(parts, idx [P, tau, b_max], theta_d, theta_u, batch, dropped);
        rounds are planned in order, each advancing the participation
        record of the participants whose upload was not lost."""
        s = self.s
        rng = _rng(self.seed, KIND_SAMPLING, t)
        parts = rng.choice(s.n_clients, s.n_part, replace=False)
        idx = np.empty((len(parts), s.tau, s.b_max), np.int64)
        for i, c in enumerate(parts):
            idx[i] = rng.choice(self.splits[c], size=(s.tau, s.b_max),
                                replace=True)
        mu, bw_d, bw_u = self.devices.snapshot(t)
        mask = np.zeros(s.n_clients, bool)
        mask[parts] = True
        # the device reads copies: ``last`` changes below, and a host
        # array handed to jnp.asarray may be read only when the call runs
        td_all, b_all = _plan_jit(
            jnp.array(self.last, copy=True), jnp.int32(t),
            jnp.array(mask, copy=True), self.theta_u,
            jnp.array(bw_d, jnp.float32), jnp.array(bw_u, jnp.float32),
            jnp.array(mu, jnp.float32), self.q_bits, s)
        td, batch = np.asarray(td_all)[parts], np.asarray(b_all)[parts]
        # a participant drops out after training with the round's first P
        # uniforms of its fault stream below the rate; it is not recorded
        dropped = _rng(self.seed, KIND_FAULTS, t).random(len(parts)) \
            < s.dropout_rate
        self.last[parts[~dropped]] = t
        return (parts, idx, td, np.asarray(self.theta_u)[parts], batch,
                dropped)


def planned_samples(s: Settings, seed: int, splits, label_dist, volumes,
                    q_bits: float, first: int, last: int) -> int:
    """Samples the plans of rounds first..last train: sum of tau * b_i."""
    pl = Planner(s, seed, splits, label_dist, volumes, q_bits)
    total = 0
    for t in range(1, last + 1):
        batch = pl.plan(t)[4]
        if t >= first:
            total += s.tau * int(np.sum(batch))
    return total


class Reference:
    """Runs rounds 1..k of the cell's federation from the seed."""

    def __init__(self, s: Settings, seed: int, model, data, splits,
                 label_dist, volumes, *, mode: str = "reference",
                 fault: str = "none", chunk: int = 8):
        if mode not in ("reference", "control"):
            raise ValueError(f"unknown mode {mode!r}")
        if fault not in ("none", "half_cohort", "no_down_bits"):
            raise ValueError(f"unknown fault {fault!r}")
        self.s, self.fault = s, fault
        self.xtr, self.ytr = data[0], data[1]
        self.params0 = model.init(jax.random.PRNGKey(seed),
                                  n_classes=int(data[1].max()) + 1)
        leaves, self.treedef = jax.tree_util.tree_flatten(self.params0)
        self.shapes = [l.shape for l in leaves]
        self.sizes = [int(l.size) for l in leaves]
        self.global_f = self.flatten(self.params0)
        self.global_init = self.global_f
        self.n = int(self.global_f.shape[0])
        self.planner = Planner(s, seed, splits, label_dist, volumes,
                               float(self.n * FULL_BITS))
        self.locals: dict = {}
        self.chunk = chunk
        self.tiers: dict = {}
        self.bits = 0.0
        self._train = self._make_train(model.apply, mode)

    def flatten(self, tree):
        return jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                for l in jax.tree_util.tree_leaves(tree)])

    def unflatten(self, flat):
        out, off = [], 0
        for shape, size in zip(self.shapes, self.sizes):
            out.append(flat[off:off + size].reshape(shape))
            off += size
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def _make_train(self, apply, mode):
        # the cells state float32 weights and updates with convolution and
        # matmul inputs rounded to bfloat16 (one MXU pass, JAX's default on
        # a TPU): the reference computes them so, the control with inputs
        # rounded to float8 first, the step below bfloat16
        operand = None if mode == "reference" else jnp.float8_e4m3fn

        def loss(p, x, y, w):
            logits = apply(p, x, jax.lax.Precision.DEFAULT, operand)
            ll = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                     y[:, None], axis=-1)[:, 0]
            return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)

        def one(global_f, local, theta_d, theta_u, xs, ys, ws, lr):
            w0, down_bits = download(global_f, local, theta_d)
            p = self.unflatten(w0)

            def step(p, inp):
                x, y, w = inp
                g = jax.grad(loss)(p, x, y, w)
                return jax.tree.map(lambda a, b: a - lr * b, p, g), None

            p, _ = jax.lax.scan(step, p, (xs, ys, ws))
            w1 = self.flatten(p)
            up, up_bits = upload(w0 - w1, theta_u)
            return up, w1, down_bits, up_bits

        return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, 0, 0, 0, None)))

    def run_round(self, t: int):
        """Round t (rounds run in order from 1): the new global model."""
        s = self.s
        parts, idx, td, tu, batch, dropped = self.planner.plan(t)
        byz = self.planner.byz[parts]
        for b in batch:
            key = tier_of(int(b), s)
            self.tiers[key] = self.tiers.get(key, 0) + 1
        lr = jnp.float32(s.lr) * jnp.float32(s.lr_decay) ** jnp.float32(t - 1)
        ws_row = (np.arange(s.b_max)[None, :] < batch[:, None]).astype(
            np.float32)
        # uploads that reach the server: not dropped, and for the
        # half-cohort fault only the first half of the participants
        agg = ~dropped
        if self.fault == "half_cohort":
            agg &= np.arange(len(parts)) < len(parts) // 2
        rows, new_locals = [], {}
        for c0 in range(0, len(parts), self.chunk):
            cp = parts[c0:c0 + self.chunk]
            k = len(cp)
            pad = self.chunk - k
            sl = np.concatenate([np.arange(c0, c0 + k),
                                 np.full(pad, c0, np.int64)])
            locs = jnp.stack([self.locals.get(int(c), self.global_init)
                              for c in parts[sl]])
            ws = np.repeat(ws_row[sl][:, None, :], s.tau, axis=1)
            ws[k:] = 0.0
            ups, w1, db, ub = self._train(
                self.global_f, locs, jnp.asarray(td[sl]),
                jnp.asarray(tu[sl]), jnp.asarray(self.xtr[idx[sl]]),
                jnp.asarray(self.ytr[idx[sl]]), jnp.asarray(ws), lr)
            sent = ~dropped[c0:c0 + k]
            if self.fault != "no_down_bits":
                self.bits += float(np.sum(np.asarray(db)[:k]))
            self.bits += float(np.sum(np.asarray(ub)[:k] * sent))
            for j, c in enumerate(cp):
                i = c0 + j
                if agg[i]:
                    # an attacker sends its sparse update sign-flipped and
                    # scaled
                    rows.append(ups[j] * (-s.attack_scale if byz[i]
                                          else 1.0))
                if not dropped[i]:
                    new_locals[int(c)] = w1[j]
        self.locals.update(new_locals)
        self.global_f = self.global_f - aggregate(rows, s, self.n)
        return self.global_f
