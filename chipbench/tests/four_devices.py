"""Row-sharded corpora on four host devices, in a process of their own.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
      python chipbench/tests/four_devices.py layout
  ... python chipbench/tests/four_devices.py harness <root> <workload>

prints one JSON object with what ``test_chipbench_shards.py`` checks.  The
generator's blocks are made small (``BLOCK_ROWS``), so that each shard
holds several of them.
"""
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from chipbench import compare, datagen, harness, reference  # noqa: E402

SEED = 2 ** 31 + 5
N, BLOCK = 4096, 256


def sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def _raises(fn) -> str:
    try:
        fn()
    except ValueError as e:
        return str(e)
    return ""


def layout() -> dict:
    out = {"devices": len(jax.devices())}
    # another n than below: the block size is read when ``manifold`` is
    # traced, and a traced program is kept per n
    datagen.BLOCK_ROWS = 512
    out["sha_one_device_2048_block512"] = sha(datagen.make("manifold", 7, 0, 2048))
    datagen.BLOCK_ROWS = BLOCK
    X1 = datagen.make("manifold", SEED, 0, N)
    for shards in (2, 4):
        Xs = datagen.make("manifold", SEED, 0, N,
                          mesh=datagen.corpus_mesh(N, shards, 4))
        out[f"equal_{shards}"] = bool(np.array_equal(np.asarray(Xs),
                                                     np.asarray(X1)))
        out[f"rows_{shards}"] = sorted(
            (s.device.id, s.data.shape[0]) for s in Xs.addressable_shards)

    # the reference over one shared corpus: column blocks of 512 in both
    Xs = jax.device_put(np.asarray(X1), NamedSharding(
        datagen.corpus_mesh(N, 4, 4), P(datagen.AXIS)))
    Q = np.asarray(datagen.make("manifold", SEED, 1, 300))
    kw = dict(query_block=128, col_cap=512)
    d1, i1 = reference.exact_topk(Q, X1, 10, **kw)
    ds, is_ = reference.exact_topk(Q, Xs, 10, **kw)
    out["reference_ids_equal"] = bool(np.array_equal(i1, is_))
    out["reference_dist_equal"] = bool(np.array_equal(d1, ds))
    out["reference_ids_in_every_shard"] = sorted(set((is_ // (N // 4)).ravel().tolist()))

    # a row repeated in shards 0 and 2: the tie goes to the lower id
    Xt = np.asarray(X1).copy()
    Xt[2 * N // 4 + 5] = Xt[7]
    Xt_s = jax.device_put(Xt, NamedSharding(datagen.corpus_mesh(N, 4, 4),
                                            P(datagen.AXIS)))
    _, it = reference.exact_topk(Xt[7:8], Xt_s, 3, **kw)
    out["tie_ids"] = it[0, :2].tolist()

    ids = np.random.default_rng(0).integers(0, N, (50, 10))
    g1, gs = compare.gather_rows(X1, ids, chunk=64), compare.gather_rows(Xs, ids, chunk=64)
    out["gather_equal"] = bool(np.array_equal(g1, gs))
    out["gather_is_rows"] = bool(np.array_equal(gs, np.asarray(X1)[ids]))

    datagen.BLOCK_ROWS = 2048
    out["raises_not_whole_blocks"] = _raises(lambda: datagen.corpus_mesh(N, 4, 4))
    datagen.BLOCK_ROWS = BLOCK
    out["raises_not_dividing"] = _raises(lambda: datagen.corpus_mesh(N, 3, 4))
    out["raises_over_chips"] = _raises(lambda: datagen.corpus_mesh(N, 4, 2))
    return out


def drop_shard_offset(system) -> None:
    """Each answer's ids lose their shard's row offset: ids that lie in
    the corpus, of the wrong rows."""
    index = system.server.index
    inner = index.search

    def search(Q, k=10, **kw):
        idx, dist, comps = inner(Q, k=k, **kw)
        return idx % index.shard_size, dist, comps

    index.search = search


def harness_runs(root: str, workload: str) -> dict:
    datagen.BLOCK_ROWS = BLOCK
    out = {}
    for name, hook in (("sound", None), ("offset_dropped", drop_shard_offset)):
        res = harness.run_cell(root, workload, SEED, 1.0, False,
                               t_process=time.monotonic(),
                               require=lambda chips: harness.device_info(),
                               program_hook=hook)
        out[name] = {"correct": res["correct"], "checks": res["checks"],
                     "attempted": res["attempted"], "failed": res["failed"],
                     "recall": res["metrics"]["recall_at_10"]["value"]}
    return out


if __name__ == "__main__":
    what = sys.argv[1]
    res = layout() if what == "layout" else harness_runs(*sys.argv[2:4])
    print(json.dumps(res))
