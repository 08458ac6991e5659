"""Failure injection, heartbeat, stragglers, gradient compression."""

import time

import numpy as np
import pytest

from repro.optim.compression import topk_compress_with_ef
from repro.runtime import FailureInjector, Heartbeat, SimulatedFailure, StepTimeMonitor
from repro.runtime.straggler import rebalance_batch


class TestFailureInjector:
    def test_fires_once_at_step(self):
        inj = FailureInjector([3])
        for s in (1, 2):
            inj.maybe_fail(s)
        with pytest.raises(SimulatedFailure):
            inj.maybe_fail(3)
        inj.maybe_fail(3)  # consumed
        assert len(inj.injected) == 1

    def test_kinds(self):
        inj = FailureInjector({2: "pod-loss"})
        with pytest.raises(SimulatedFailure, match="pod-loss"):
            inj.maybe_fail(2)

    def test_thread_safe_single_injection(self):
        """Heartbeat thread and train loop racing one step inject once.

        The seed popped ``_pending`` without a lock, so two threads could
        both observe the step pending and double-inject.
        """
        import threading

        for _ in range(50):  # race-amplifying repetition
            inj = FailureInjector([7])
            raised = []
            barrier = threading.Barrier(4)

            def hammer():
                barrier.wait()
                try:
                    inj.maybe_fail(7)
                except SimulatedFailure as e:
                    raised.append(e)

            ts = [threading.Thread(target=hammer) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=10)
            assert len(raised) == 1, "one configured step injected more than once"
            assert len(inj.injected) == 1


class TestHeartbeat:
    def test_stall_detected(self):
        stalls = []
        with Heartbeat(timeout_s=0.2, on_stall=stalls.append) as hb:
            time.sleep(0.7)
        assert hb.stalls >= 1
        assert stalls and stalls[0] > 0.2

    def test_no_stall_when_beating(self):
        with Heartbeat(timeout_s=0.5) as hb:
            for _ in range(6):
                hb.beat()
                time.sleep(0.05)
        assert hb.stalls == 0


class TestStraggler:
    def test_detection_and_mitigation_gain(self):
        mon = StepTimeMonitor(n_hosts=8)
        times = {h: 1.0 + 0.01 * h for h in range(8)}
        times[5] = 3.0  # straggler
        for _ in range(5):
            rep = mon.record(times)
        assert 5 in rep.flagged
        assert set(rep.flagged) == {5}
        # rebalancing strictly beats the synchronous barrier
        assert mon.mitigated_step_time() < mon.synchronous_step_time()
        # straggler gets the smallest share
        split = rebalance_batch(256, rep.weights)
        assert sum(split.values()) == 256
        assert split[5] == min(split.values())

    def test_uniform_hosts_not_flagged(self):
        mon = StepTimeMonitor(n_hosts=4)
        for _ in range(5):
            rep = mon.record({h: 1.0 + 0.001 * h for h in range(4)})
        assert not rep.flagged

    def test_rebalance_exact_total(self):
        w = {0: 1.3, 1: 0.9, 2: 0.8}
        split = rebalance_batch(100, w)
        assert sum(split.values()) == 100


class TestCompression:
    def test_ratio_and_shapes(self):
        rng = np.random.default_rng(0)
        grads = {"a": rng.normal(size=(100, 100)).astype(np.float32), "b": rng.normal(size=(50,)).astype(np.float32)}
        sparse, ef, stats = topk_compress_with_ef(grads, None, ratio=0.01)
        assert stats["ratio"] <= 0.03
        nz = np.count_nonzero(sparse["a"])
        assert nz == max(1, int(100 * 100 * 0.01))
        assert sparse["a"].shape == grads["a"].shape

    def test_error_feedback_conserves_mass(self):
        """sent + residual == grad + prior residual (no signal lost)."""
        rng = np.random.default_rng(1)
        g = {"w": rng.normal(size=(64, 64)).astype(np.float32)}
        ef = None
        total_sent = np.zeros((64, 64), np.float32)
        total_grad = np.zeros((64, 64), np.float32)
        for step in range(10):
            gi = {"w": rng.normal(size=(64, 64)).astype(np.float32)}
            total_grad += gi["w"]
            sparse, ef, _ = topk_compress_with_ef(gi, ef, ratio=0.05)
            total_sent += np.asarray(sparse["w"], np.float32)
        residual = np.asarray(ef["w"])
        np.testing.assert_allclose(total_sent + residual, total_grad, rtol=1e-4, atol=1e-4)

    def test_ef_eventually_transmits_small_coords(self):
        """A coordinate too small to win top-k accumulates via EF until sent."""
        big = {"w": np.zeros(100, np.float32)}
        big["w"][0] = 10.0
        small = {"w": np.full((100,), 0.01, np.float32)}
        small["w"][0] = 0.0
        ef = None
        sent_total = np.zeros(100, np.float32)
        # one dominant step, then steady small grads: EF residuals from the
        # small coords must eventually win top-1 and get transmitted
        sparse, ef, _ = topk_compress_with_ef(big, ef, ratio=0.01)
        sent_total += np.asarray(sparse["w"])
        for _ in range(10):
            sparse, ef, _ = topk_compress_with_ef(small, ef, ratio=0.01)
            sent_total += np.asarray(sparse["w"])
        assert (sent_total[1:] > 0).any()  # small coords escaped via EF


HOST_IMPORTS_SCRIPT = """
import sys
import test_dstore_chaos
from repro.core.dstore import DistributedStore
from repro.core.resilience import CircuitOpen
from repro.core.tiers import TierError
from repro.data.pipeline import plan_shard_placement
from repro.runtime.failure import ChaosInjector
print("jax" in sys.modules)
"""


def test_store_host_processes_never_import_jax():
    """The store's spawned host processes (the zero-data-loss soak, the
    killed-owner test) import only these modules.  Without JAX in the
    process they cannot initialise a backend, so they never contend for
    the chip with the parent that holds it."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([os.path.join(root, d) for d in ("src", "tests")]))
    proc = subprocess.run(
        [sys.executable, "-c", HOST_IMPORTS_SCRIPT],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip() == "False"
