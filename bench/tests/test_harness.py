"""A whole run, less the look for a chip, at a tiny size on the CPU: a sound
run is correct, and the control and each fault of the timed path are not.

The control is the program's int8 path-metric pipeline (the nearest lower
precision it has). The faults are planted under the served path: a decoded
bit altered where it is produced, half of each launch's lanes left out,
the lanes of every chip but the first left out of a mesh launch's result
(four virtual CPU devices), and a dispatch step that leaves every session
as it was. Open-loop mixes, which no cell uses yet, run here too.
"""

import time

import pytest

import harness


# the four-chip cell, ready for BENCHMARK.json once it has run on a four-chip host
X4_CELL = {"name": "ccsds-x4.playback", "config": "ccsds-r12-x4", "traffic": "playback", "chips": 4}


def load(workload: str):
    if workload != X4_CELL["name"]:
        return harness.load_cell(workload)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    config = harness.load_json(harness.BENCH / "configs" / f"{X4_CELL['config']}.json")
    return bench, X4_CELL, config, harness.load_mix(X4_CELL["traffic"])


def tiny(workload: str, **mix_updates):
    bench, cell, config, mix = load(workload)
    config, mix = dict(config), dict(mix)
    if mix["chunks_per_stream"] == 1:  # one frame per stream
        config.update(D=64, L=14)
        mix.update(clients_per_chip=8, chunk_stages=48, pool_chunks=16, warmup_requests_per_client=2)
    else:
        config.update(D=64, L=12)
        config["service"] = dict(config["service"], max_batch_blocks=16 * cell["chips"])
        mix.update(clients_per_chip=2, chunk_stages=256, pool_chunks=8,
                   warmup_requests_per_client=4, check={"keep_every": 1, "max_chunks": None})
    mix.update(mix_updates)
    return bench, cell, config, mix


def run(workload, mix_updates=None, seconds=1.0, **kw):
    bench, cell, config, mix = tiny(workload, **(mix_updates or {}))
    kw.setdefault("backend", "ref")
    return harness.run_cell(
        bench, cell, config, mix, seed=2**31 + 17, seconds=seconds, trace=False,
        t_start=time.perf_counter(), warmup_timeout_s=20.0, grace_s=3.0, **kw,
    )


@pytest.fixture
def broken(monkeypatch):
    """Plant a fault under DecoderEngine._decode_blocks."""
    from repro.core.engine import DecoderEngine

    orig = DecoderEngine._decode_blocks

    def plant(fault):
        def patched(self, blocks, frame_counts, interpret):
            bits = orig(self, blocks, frame_counts, interpret)
            n = bits.shape[1]
            if fault == "flip":
                return bits.at[0, 0].set(1 - bits[0, 0])
            if fault == "half":
                return bits.at[:, n // 2 :].set(0)
            if fault == "first_chip_only":  # no lanes come back from the other chips
                return bits.at[:, blocks.shape[2] // self.n_shards :].set(0)
            raise ValueError(fault)

        monkeypatch.setattr(DecoderEngine, "_decode_blocks", patched)

    return plant


CELLS = ["ccsds.playback", "is95.frames", X4_CELL["name"]]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"], r["checks"]
    assert r["checks"]["checked_blocks"]["value"] > 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = run(workload, metric_mode="i8")
    assert not r["correct"]
    assert r["checks"]["mismatched_bits"]["value"] > 0


@pytest.mark.parametrize("fault", ["flip", "half"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, broken):
    broken(fault)
    r = run(workload)
    assert not r["correct"], r["checks"]


def test_leaving_out_the_other_chips_is_not_correct(broken):
    broken("first_chip_only")
    r = run(X4_CELL["name"])
    assert not r["correct"], r["checks"]
    assert r["checks"]["mismatched_bits"]["value"] > 0


OPEN_LOOPS = {
    "poisson": {"kind": "poisson", "rate_hz": 40.0},
    "periodic": {"kind": "periodic", "period_ms": 20.0},
    "onoff": {"kind": "onoff", "rate_hz": 80.0, "on_ms": 100.0, "off_ms": 100.0},
}


@pytest.mark.parametrize("arrivals", sorted(OPEN_LOOPS))
@pytest.mark.parametrize("workload", ["ccsds.playback", "is95.frames"])
def test_open_loop_is_correct_and_timed_from_due(workload, arrivals):
    record = {}
    r = run(workload, mix_updates={"arrivals": OPEN_LOOPS[arrivals]}, seconds=1.5, record=record)
    assert r["correct"], r["checks"]
    reqs = record["requests"]
    assert reqs and r["attempted"] == len(reqs)
    assert r["failed"] == sum(1 for _, _, done in reqs if done is None)
    for due, sent, done in reqs:  # a chunk never sent was never answered
        assert (sent is None and done is None) or (sent >= due and (done is None or done >= sent))
    assert len(record["run"].late_s) == sum(1 for _, sent, _ in reqs if sent is not None)


def test_due_times_follow_the_schedule():
    import numpy as np

    from generator import due_times

    def take(arrivals, n, seed=5):
        it = due_times(arrivals, np.random.default_rng(seed))
        return np.array([next(it) for _ in range(n)])

    t = take({"kind": "periodic", "period_ms": 20.0}, 50)
    assert 0 <= t[0] < 0.02 and np.allclose(np.diff(t), 0.02)
    t = take({"kind": "poisson", "rate_hz": 100.0}, 20000)
    assert np.all(np.diff(t) > 0) and abs(len(t) / t[-1] - 100.0) < 3.0
    on = take({"kind": "onoff", "rate_hz": 100.0, "on_ms": 200.0, "off_ms": 600.0}, 20000)
    assert np.all(np.diff(on) > 0) and abs(len(on) / on[-1] - 25.0) < 2.5  # ON a quarter of the time
    assert np.array_equal(t, take({"kind": "poisson", "rate_hz": 100.0}, 20000))


def test_step_that_changes_nothing_is_not_correct(monkeypatch):
    from repro.launch.serve_decoder import SessionPool

    monkeypatch.setattr(SessionPool, "step", lambda self, isolate=False: 0)
    r = run("ccsds.playback")
    assert not r["correct"]
    assert r["checks"]["failed_streams"]["value"] > 0
