"""FL substrate integration tests: Track-A simulator, partitioner, capability."""

import numpy as np
import pytest

from repro.core.caesar import CaesarConfig
from repro.data import partition, synthetic
from repro.fl.capability import CapabilityModel
from repro.fl.simulation import SimConfig, Simulator


class TestPartition:
    def test_iid_equal_volumes(self):
        labels = np.random.default_rng(0).integers(0, 10, 1000)
        splits, ld, vol = partition.dirichlet_partition(labels, 10, p=0.0)
        assert all(abs(v - 100) <= 1 for v in vol)

    def test_heterogeneity_increases_kl(self):
        labels = np.random.default_rng(0).integers(0, 10, 20000)
        kls = []
        for p in [1, 5, 10]:
            _, ld, _ = partition.dirichlet_partition(labels, 20, p=p, seed=1)
            e = np.clip(ld, 1e-12, 1)
            kls.append(np.mean(np.sum(e * np.log(e * 10), axis=1)))
        assert kls[0] < kls[1] < kls[2]

    def test_every_client_has_data(self):
        labels = np.random.default_rng(0).integers(0, 6, 5000)
        splits, _, vol = partition.dirichlet_partition(labels, 50, p=10)
        assert (vol >= 8).all()


class TestCapability:
    def test_modes_change_every_20_rounds(self):
        cap = CapabilityModel(16, seed=0)
        mu1, _, _ = cap.snapshot(1)
        mu19, _, _ = cap.snapshot(19)
        mu21, _, _ = cap.snapshot(21)
        np.testing.assert_allclose(mu1, mu19)      # same mode epoch
        assert not np.allclose(mu1, mu21)          # re-drawn

    def test_bandwidth_in_paper_range(self):
        cap = CapabilityModel(32, seed=1)
        _, bd, bu = cap.snapshot(3)
        assert bd.min() >= 1e6 and bd.max() <= 30e6

    def test_seed_streams_do_not_collide(self):
        """SeedSequence spawn keys replace the arithmetic seeds
        (seed*100003 + epoch / seed*7919 + t), under which e.g. seed=0
        collapsed every mode epoch onto nearly the same stream and
        (seed, t) pairs collided across seeds (seed=1 at t equaled seed=0
        at 7919 + t)."""
        a = CapabilityModel(16, seed=0)
        b = CapabilityModel(16, seed=1)
        # seed=0 must still re-draw modes across epochs
        mode0 = a.snapshot(1)[0] / a._tier
        mode1 = a.snapshot(21)[0] / a._tier
        assert not np.allclose(mode0, mode1)
        # the old collision pair: seed*7919 + t is equal for
        # (seed=0, t=7919) and (seed=1, t=0) — raw draws must now differ
        _, bd_a, _ = a.snapshot(7919)
        _, bd_b, _ = b.snapshot(0)
        assert not np.allclose(bd_a / a._bw_tier, bd_b / b._bw_tier)
        # deterministic: same (seed, t) ⇒ same snapshot
        np.testing.assert_array_equal(
            a.snapshot(5)[1], CapabilityModel(16, seed=0).snapshot(5)[1])


def _cfg(**kw):
    base = dict(dataset="har", rounds=8, n_clients=24, data_scale=0.25,
                eval_every=4, participation=0.25,
                dataset_kwargs={"sep": 2.2, "noise": 1.5},  # easy variant
                caesar=CaesarConfig(tau=5, b_max=16))
    base.update(kw)
    return SimConfig(**base)


class TestSimulator:
    def test_caesar_learns(self):
        h = Simulator(_cfg()).run()
        assert h.accuracy[-1] > 0.5          # synthetic task is separable
        assert h.traffic_bits[-1] > 0
        assert h.sim_time[-1] > 0

    def test_traffic_strictly_below_fedavg(self):
        h_c = Simulator(_cfg()).run()
        h_f = Simulator(_cfg(scheme="fedavg")).run()
        assert h_c.traffic_bits[-1] < h_f.traffic_bits[-1]

    @pytest.mark.parametrize("scheme", ["fic", "cac", "flexcom", "prowd",
                                        "pyramidfl"])
    def test_baselines_run(self, scheme):
        h = Simulator(_cfg(scheme=scheme, rounds=4)).run()
        assert len(h.accuracy) >= 1
        assert np.isfinite(h.accuracy[-1])

    def test_staleness_bookkeeping(self):
        sim = Simulator(_cfg(rounds=4))
        sim.run()
        lr = np.asarray(sim.caesar_state.last_round)
        assert lr.max() >= 1                 # someone participated
        assert (lr >= 0).all()

    def test_batch_opt_reduces_waiting_vs_fixed(self):
        cfg_on = _cfg(rounds=6)
        cfg_off = _cfg(rounds=6, caesar=CaesarConfig(
            tau=5, b_max=16, use_batch_opt=False))
        # waiting[-1] is the running mean over every simulated round
        w_on = Simulator(cfg_on).run().waiting[-1]
        w_off = Simulator(cfg_off).run().waiting[-1]
        assert w_on <= w_off + 1e-6

    def test_history_to_target(self):
        h = Simulator(_cfg()).run()
        hit = h.to_target(0.0)
        assert hit is not None and hit[2] >= 1

    def test_waiting_history_is_round_aligned_running_mean(self):
        """History.waiting is an eval-aligned RUNNING MEAN over every
        simulated round (not a 1-in-eval_every subsample); the raw
        per-round samples live in waiting_per_round/wall_per_round."""
        h = Simulator(_cfg(rounds=8, eval_every=4)).run()
        assert len(h.waiting) == len(h.rounds) == 2
        assert len(h.waiting_per_round) == len(h.wall_per_round) == 8
        for i, t in enumerate(h.rounds):
            np.testing.assert_allclose(
                h.waiting[i], np.mean(h.waiting_per_round[:t]), rtol=1e-9)

    def test_eq7_time_model_consistent_with_planner(self):
        """Accounting regression: measured round time / barrier waiting use
        the SAME Eq.-7 θ·Q/β model the Eq. 8–9 planner equalizes — the
        Eq.-8 leader (fastest participant, runs at b_max) must attain the
        round's max time, i.e. no phantom barrier from a second,
        payload-bits-based time model."""
        from repro.core import batchsize as bs
        cfg = _cfg(rounds=6)
        sim = Simulator(cfg)
        rec = []
        orig_plan = sim.planner.plan

        def spy(t, parts, mu, bw_d, bw_u):
            out = orig_plan(t, parts, mu, bw_d, bw_u)
            rec.append((t, parts, mu, bw_d, bw_u, out))
            return out
        sim.planner.plan = spy
        h = sim.run()
        q = float(sim.model_bits)
        tau = cfg.caesar.tau
        for i, (t, parts, mu, bw_d, bw_u, out) in enumerate(rec):
            theta_d, theta_u, batch, taus = out
            times = np.asarray(bs.round_times(
                np.asarray(theta_d, np.float32),
                np.asarray(theta_u, np.float32), q,
                np.asarray(bw_d[parts], np.float32),
                np.asarray(bw_u[parts], np.float32), tau,
                np.asarray(batch, np.float32),
                np.asarray(mu[parts], np.float32)))
            # the planner gave b_max to the fastest participant; that
            # leader's planned time is the barrier for every participant
            # the Eq.-9 equalization is FEASIBLE for. Participants whose
            # communication alone exceeds the leader's time are pinned at
            # b_min (they cannot run fewer than b_min samples) — those are
            # genuine stragglers, not a phantom barrier; nobody else may
            # exceed the leader.
            leaders = np.flatnonzero(batch == cfg.caesar.b_max)
            assert leaders.size >= 1
            t_lead = times[leaders].max()
            over = times > t_lead * (1 + 1e-5)
            assert np.all(batch[over] == cfg.caesar.b_min), \
                f"round {t}: unclipped participant above the Eq.-8 leader"
            equalizable = batch > cfg.caesar.b_min
            np.testing.assert_allclose(times[equalizable].max(), t_lead,
                                       rtol=1e-5)
            # and the measured metric agrees with the Eq.-7 model
            np.testing.assert_allclose(
                h.waiting_per_round[i], np.mean(times.max() - times),
                rtol=1e-4)


class TestSyntheticData:
    def test_shapes_match_paper(self):
        d = synthetic.cifar10_like(scale=0.01)
        assert d.x_train.shape[1:] == (32, 32, 3) and d.n_classes == 10
        d = synthetic.har_like(scale=0.1)
        assert d.x_train.shape[1:] == (128, 9) and d.n_classes == 6
        d = synthetic.speech_like(scale=0.01)
        assert d.x_train.shape[1:] == (4000, 1) and d.n_classes == 35
        d = synthetic.oppo_ts_like(scale=0.01)
        assert d.n_classes == 2
