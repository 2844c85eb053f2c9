"""Whole runs of a tiny cell on a 1x4 mesh of host devices, for
``test_mesh.py``:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 -m chipbench.tests.mesh_runs <dir> <out.json>

JAX fixes its device count when it starts, so the test runs this in a
process of its own. It writes one JSON object: the sharded weight draw
against the one-device draw, a sound run with its control, a run with each
fault a cell on a mesh can have, and a run whose plan falls back.
"""
from __future__ import annotations

import json
import sys
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import drive, faults, reference, run, spec  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

SEED = 2 ** 31 + 19


def weights(root: Path, name: str) -> dict:
    """The mesh draw against the one-device draw, leaf by leaf."""
    from repro.distributed.sharding import make_param_shardings
    from repro.launch.mesh import make_serving_mesh
    cell = spec.load_cell(root, name)
    mesh = make_serving_mesh(drive.mesh_shape(cell.config))
    key = reference.weights_key(SEED)
    one = run.draw_weights(cell.arch, cell.config, key)
    sharded = run.draw_weights(cell.arch, cell.config, key, mesh)
    want = make_param_shardings(sharded, mesh)
    leaves = jax.tree_util.tree_leaves_with_path(sharded)
    out = {"leaves": len(leaves), "equal": [], "sharded": [],
           "largest_shard_share": 0.0}
    for (path, x), a, sh in zip(leaves, jax.tree.leaves(one),
                                jax.tree.leaves(want)):
        key_s = jax.tree_util.keystr(path)
        same = (np.asarray(x).view(np.uint8).tobytes()
                == np.asarray(a).view(np.uint8).tobytes())
        out["equal"].append([key_s, bool(same and x.dtype == a.dtype)])
        out["sharded"].append([key_s, x.sharding == sh])
        if x.sharding.spec != sh.spec or any(s is not None for s in sh.spec):
            big = max(s.data.size for s in x.addressable_shards)
            out["largest_shard_share"] = max(out["largest_shard_share"],
                                             big / x.size)
    # the engine puts its parameters in these shardings: no second copy
    moved = jax.device_put(sharded, want)
    out["engine_put_copies"] = sum(
        int(s.data.unsafe_buffer_pointer() != t.data.unsafe_buffer_pointer())
        for x, y in zip(jax.tree.leaves(sharded), jax.tree.leaves(moved))
        for s, t in zip(x.addressable_shards, y.addressable_shards))
    return out


def one_run(root: Path, name: str, **kw) -> dict:
    res = run.run_cell(root, name, SEED, 1.0, False, require_tpu=False,
                       keep_readings=True, **kw)
    r = res.pop("readings")
    res["readings"] = {"mean_gap": r.number("mean_gap"),
                       "mean_gap_in_doubt": r.number("mean_gap_in_doubt")}
    if r.control_gaps is not None:
        res["readings"]["control_mean_gap"] = r.number("mean_gap", True)
        res["readings"]["control_mean_gap_in_doubt"] = r.number(
            "mean_gap_in_doubt", True)
    return res


def main(out_dir: str, out_path: str) -> int:
    root = Path(out_dir)
    name = tiny.make_tree(root / "mesh", config=tiny.MESH_CONFIG,
                          traffic=tiny.MESH_TRAFFIC, cell=tiny.MESH_CELL,
                          name="tinytp.chat", chips=4)
    out = {"devices": len(jax.devices()),
           "weights": weights(root / "mesh", name),
           "sound": one_run(root / "mesh", name, control=True),
           "faults": {}}
    build = drive.build_engine
    for fault in faults.FAULTS + faults.MESH_FAULTS:
        drive.build_engine = faults.broken(build, fault,
                                           tiny.CONFIG["vocab_size"])
        try:
            out["faults"][fault] = one_run(root / "mesh", name)
        finally:
            drive.build_engine = build
    # 3 lanes on a 2x2 mesh do not divide its data axis: the plan falls
    # back (REASON_NONDIVISIBLE_MESH), so the run fails
    bad = tiny.make_tree(root / "fallback", config=dict(
        tiny.MESH_CONFIG, serve=dict(tiny.MESH_CONFIG["serve"],
                                     mesh_shape=[2, 2])),
        cell={"lanes": 3}, name="tinyfb.chat", chips=4)
    try:
        run.run_cell(root / "fallback", bad, SEED, 1.0, False,
                     require_tpu=False)
        out["fallback"] = "ran"
    except RuntimeError:
        out["fallback"] = traceback.format_exc().strip().splitlines()[-1]
    Path(out_path).write_text(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
