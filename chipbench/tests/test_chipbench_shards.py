"""A corpus row-sharded over a cell's chips: made, judged and traced
without holding it on one device.  The four-device cases run in a process
of their own (``four_devices.py``): the tests' process sees one device."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import datagen, tracing
from test_chipbench_harness import REPO, TINY, make_root, root  # noqa: F401

SCRIPT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "four_devices.py")

#: ``datagen.make`` on the parent of the sharded generator (sha256 of the
#: bytes, this CPU backend): the one-device path must make the same bytes
PARENT_SHA = {
    (2 ** 31 + 3, 0, 6000):
        "01857bb34249f9e6c734e216e71e550b4593609b733051bab0a92ad0904a0685",
    (2 ** 31 + 3, 1, 256):
        "12e025246c5ee3b814348e390ff136838008e5f127acfe6893ecaf794892b12a",
    # seed 7, stream 0, 2048 rows in blocks of 512 (four_devices.py; the
    # parent with its block cap, ``_block_rows``'s default, set to 512)
    "block512": "7502856ae4ea9a860beabc31eccea4d08c1f52bad20b20b75c3feaa5ee73e98f",
}

TINY_SHARDED = dict(
    TINY, name="tiny-sharded", engine="sharded",
    engine_cfg={"engine": "brute", "shards": 4},
    data={"generator": "manifold", "n": 4096, "query_pool": 256, "shards": 4,
          "params": {"d": 96}},
    correct={"bad_answers": 0, "dist_err": 1.2e-6, "rank_gap": 0.0})


def _four_devices(*args) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, SCRIPT, *args], capture_output=True,
                       text=True, env=env, timeout=600, cwd=REPO)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def layout():
    return _four_devices("layout")


@pytest.mark.parametrize("key", [k for k in PARENT_SHA if k != "block512"],
                         ids=["corpus", "queries"])
def test_one_device_corpus_keeps_the_parents_bytes(key):
    import hashlib

    seed, stream, n = key
    a = np.asarray(datagen.make("manifold", seed, stream, n))
    assert hashlib.sha256(a.tobytes()).hexdigest() == PARENT_SHA[key]


def test_one_device_corpus_in_blocks_keeps_the_parents_bytes(layout):
    assert layout["devices"] == 4
    assert layout["sha_one_device_2048_block512"] == PARENT_SHA["block512"]


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_corpus_is_the_one_device_corpus_bit_for_bit(layout, shards):
    assert layout[f"equal_{shards}"] is True
    assert layout[f"rows_{shards}"] == [[d, 4096 // shards]
                                        for d in range(shards)]


def test_sharded_reference_is_the_one_device_reference(layout):
    assert layout["reference_ids_equal"] is True
    assert layout["reference_dist_equal"] is True
    assert layout["reference_ids_in_every_shard"] == [0, 1, 2, 3]


def test_sharded_reference_gives_a_tie_to_the_lowest_id(layout):
    # row 7 of shard 0 repeated as row 5 of shard 2, queried by itself
    assert layout["tie_ids"] == [7, 2 * 1024 + 5]


def test_gather_rows_reads_each_row_from_its_shard(layout):
    assert layout["gather_equal"] is True
    assert layout["gather_is_rows"] is True


@pytest.mark.parametrize("case,words", [
    ("raises_not_whole_blocks", ["1024", "2048-row blocks"]),
    ("raises_not_dividing", ["4096", "3 shards"]),
    ("raises_over_chips", ["4 shards", "2 chip"]),
])
def test_a_layout_that_cannot_hold_the_corpus_raises(layout, case, words):
    assert all(w in layout[case] for w in words), layout[case]


def test_a_run_names_the_sizes_where_the_layout_does_not_fit(root):
    from chipbench import harness

    _add_sharded_cell(root, chips=2)
    with pytest.raises(ValueError, match="4 shards need 4 devices; the cell "
                                         "has 2 chip"):
        harness.run_cell(root, "tiny-sharded.open", 1, 1.0, False,
                         t_process=0.0,
                         require=lambda chips: harness.device_info())


def _add_sharded_cell(root: str, chips: int = 4) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(os.path.join(root, "chipbench", "configs",
                           "tiny-sharded.json"), "w") as f:
        json.dump(TINY_SHARDED, f)
    bench["configs"].append({"name": "tiny-sharded", "source": "test",
                             "file": "chipbench/configs/tiny-sharded.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-sharded.open",
                               "config": "tiny-sharded",
                               "traffic": "poisson_tiny", "chips": chips,
                               "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)


@pytest.fixture(scope="module")
def four_shard_runs(tmp_path_factory):
    base = make_root(tmp_path_factory.mktemp("sharded"))
    _add_sharded_cell(base)
    return _four_devices("harness", base, "tiny-sharded.open")


def test_four_shard_run_on_the_cpu_is_correct(four_shard_runs):
    res = four_shard_runs["sound"]
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["rank_gap"]["value"] == 0.0
    assert res["attempted"] == 150 and res["failed"] == 0
    assert res["recall"] == pytest.approx(1.0)


def test_four_shard_run_without_the_shard_offset_is_not_correct(four_shard_runs):
    res = four_shard_runs["offset_dropped"]
    assert res["correct"] is False
    # ids of the wrong rows, some repeated within an answer
    c = res["checks"]
    assert c["dist_err"]["value"] > c["dist_err"]["limit"]
    assert c["rank_gap"]["value"] > 0


def _plane(i):
    return f"/device:TPU:{i}"


def test_device_ms_per_batch_is_per_chip_on_four_chips():
    from chipbench.layers import device_ms_per_batch

    ev = [("/host:CPU", "python", tracing.WINDOW_SPAN, 0.0, 1e6, ""),
          ("/host:CPU", "python", tracing.QUERY_SPAN, 0.0, 1e6, "8")]
    for i in range(4):
        ev += [(_plane(i), tracing.OPS_LINE, "fusion", 1e5, 4e5, "jit_scan"),
               (_plane(i), tracing.MODULES_LINE, "jit_scan(3)", 1e5, 4e5, "")]
    four = tracing.reduce(ev)
    one = tracing.reduce([e for e in ev if e[0] in ("/host:CPU", _plane(0))])
    assert four["devices"] == 4 and one["devices"] == 1
    assert four["busy_s"] == one["busy_s"] == pytest.approx(4e-4)
    for red in (four, one):
        assert device_ms_per_batch({"trace": red}, [r"^jit_scan$"]) == \
            pytest.approx(0.4)
