"""The chip-mesh cell's pieces: ``mesh.shard_max_share`` and
``entries/independent_chips.py``, on hand-built results and, where JAX
has four devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``
on the CPU), in a whole tiny run."""

import json
import os

import pytest

from benchmark import harness
from benchmark.tests import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _metric():
    return harness.load_module(
        os.path.join(BENCH, "metrics", "mesh.shard_max_share.py"),
        "bench_metric_mesh_shard_max_share")


def _entry():
    return harness.load_module(
        os.path.join(BENCH, "entries", "independent_chips.py"),
        "bench_entry_independent_chips")


def _check(split, failed=False):
    c = harness.Check(0, 0.0, 1.0, 10,
                      verdicts={"keys": [True], "merged": True,
                                "configs": sum(split or []),
                                "shard_explored": split})
    if failed:
        c.error = "RuntimeError: boom"
    return c


def test_shard_max_share_is_the_mean_busiest_share():
    run = harness.Run(checks=[_check([10, 10, 10, 10]),
                              _check([40, 0, 0, 0]),
                              _check([1, 2, 3, 4], failed=True)])
    assert _metric().read(run) == pytest.approx((0.25 + 1.0) / 2)


@pytest.mark.parametrize("splits", [[], [None], [[0, 0, 0, 0]]])
def test_shard_max_share_reads_nothing_without_a_split(splits):
    run = harness.Run(checks=[_check(s) for s in splits])
    assert _metric().read(run) is None


def test_read_takes_the_batch_split_once_per_check():
    split = [5, 6, 7, 8]
    result = {"valid": False, "results": {
        0: {"valid": True, "configs_explored": 11,
            "shard_explored": split},
        1: {"valid": False, "configs_explored": 15,
            "shard_explored": split},
        2: {"valid": True, "configs_explored": 0}}}
    got = _entry().read(result, 3)
    assert got == {"keys": [True, False, True], "merged": False,
                   "configs": 26, "shard_explored": split}
    assert _entry().read({"valid": True, "results": {
        0: {"valid": True, "configs_explored": 0}}}, 1)[
            "shard_explored"] is None


def test_whole_tiny_run_over_four_devices(tmp_path):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    root, bench, bench_dir = tiny.tiny_copy(str(tmp_path), cells={})
    config = dict(tiny.CONFIGS["tiny-etcd"], name="tiny-mesh",
                  engine_opts={"chips": 4})
    with open(os.path.join(bench_dir, "configs", "tiny-mesh.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "workloads", "tiny.mesh.json"),
              "w") as f:
        json.dump({"entry": "independent_chips", "keys": 16,
                   "keys_at_once": 3, "crash_p": 0.05,
                   "corrupt_every": 4, "pool": 2,
                   "base_seed": 2**32 + 14,
                   "warmup": {"min": 2, "max": 4}}, f)
    bench["configs"].append({"name": "tiny-mesh",
                             "file": "benchmark/configs/tiny-mesh.json"})
    bench["workloads"].append({"name": "tiny.mesh", "config": "tiny-mesh",
                               "traffic": "tiny.mesh", "chips": 4})
    for m in bench["per_layer"]:
        if m["name"] == "mesh.shard_max_share":
            m["workloads"].append("tiny.mesh")
    copy = (root, bench, bench_dir)
    line = tiny.run(copy, "tiny.mesh", trace=True)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["count"] == 4
    share = line["metrics"]["mesh.shard_max_share"]["value"]
    assert 0.25 <= share <= 1.0
    line = tiny.run(copy, "tiny.mesh")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "ops_verified_per_s"}
