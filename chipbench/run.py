"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, lanes and limits are files found by
name (see ``chipbench/spec.py``). One run: refuse to run without a TPU, or
with fewer chips than the cell's ``chips``, which must be the size of the
mesh its configuration states (``serve.mesh_shape``; none is one chip);
make the weights on the device from the seed (the configuration's
architecture module, ``chipbench/arch/``), on a mesh straight into the
engine's parameter shardings, and calibrate the AQUA projections
(``chipbench/reference.py``); build the program's continuous-batching
engine, on that mesh where there is one; admit the first wave and warm
every shape the cell uses; measure for ``--seconds``
(``chipbench/drive.py``); read every device's peak memory and free the
program's cache; compare the served tokens with the float32 reference
(``chipbench/check.py``, on the same devices); print one JSON line.

With ``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line holds the
per-layer metrics, the device's busy seconds and a breakdown.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import check, drive, reference, spec, trace, traffic  # noqa: E402
from chipbench.record import Run  # noqa: E402
from chipbench.yardstick import (CompileClock, chip_peaks,  # noqa: E402
                                 device_peaks, fullest, tpu_devices)

CACHE_DIR = REPO / "chipbench" / ".jax_cache"
CORPUS = REPO / "chipbench" / "data" / "calibration.txt"


def configure_jax():
    """Persistent compile cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_layout(params, model, key):
    """The benchmark's weights must be the tree the program's model takes."""
    import jax
    want = jax.eval_shape(model.init, key)
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter tree differs from the "
                           "benchmark's weights layout")


def draw_weights(arch, conf: dict, key, mesh=None):
    """The weights from the seed, made on the device in one jitted call.
    Under a mesh every leaf is made in the shards the program's sharding
    rules give it, so no device holds the whole tree; the values are those
    of the one-device draw."""
    import jax
    init = lambda k: arch.init_params(conf, k)  # noqa: E731
    if mesh is None:
        return jax.jit(init)(key)
    from repro.distributed.sharding import make_param_shardings
    shardings = make_param_shardings(jax.eval_shape(init, key), mesh)
    return jax.jit(init, out_shardings=shardings)(key)


def check_plan(plan, backend: str, chips: int) -> None:
    """The engine must serve the configured AQUA backend on the paged
    cache through its kernel path: on one chip a plan whose only reason is
    the absent mesh, on a mesh a mesh-native plan with no reason at all."""
    from repro.core.dispatch import REASON_NO_MESH
    ok = plan.backend == backend and plan.cache_layout == "paged"
    if chips == 1:
        ok = ok and plan.reasons == (REASON_NO_MESH,)
    else:
        ok = ok and plan.mesh_native and plan.reasons == ()
    if not ok:
        raise RuntimeError(f"the engine did not plan the kernel path on "
                           f"{chips} chip(s): {plan}")


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             traced: bool, *, require_tpu: bool = True,
             t_start: float = T_START, control: bool = False,
             keep_readings: bool = False) -> dict:
    """One run of ``workload``; returns the result line as a dict (plus,
    when ``control``, the control's verdict under ``control``, and the raw
    ``readings`` when ``control`` or ``keep_readings``)."""
    cell = spec.load_cell(root, workload)
    arch, conf, mix, cp = cell.arch, cell.config, cell.traffic, cell.params
    if drive.chips(conf) != cell.chips:
        raise RuntimeError(
            f"{workload} asks for {cell.chips} chip(s), its configuration's "
            f"mesh {drive.mesh_shape(conf)} holds {drive.chips(conf)}")
    configure_jax()
    import jax
    from repro.models import build_model
    devices = (tpu_devices(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    if len(devices) < cell.chips:
        raise RuntimeError(f"{workload} needs {cell.chips} devices, JAX "
                           f"found {len(devices)}")
    dev = devices[0]
    clock = CompileClock(time.perf_counter)
    lanes, max_seq = cp["lanes"], cp["max_seq"]
    mesh = None
    if drive.mesh_shape(conf) is not None:
        # the mesh the engine builds from its ServingConfig: the first
        # ``chips`` devices, ("data", "model")
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(drive.mesh_shape(conf))

    marks = [("jax", time.perf_counter())]
    cfg = arch.program_config(conf)
    key = reference.weights_key(seed)
    params = draw_weights(arch, conf, key, mesh)
    check_layout(params, build_model(cfg), key)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    proj = reference.calibrate(arch, conf, params, reference.corpus_tokens(
        str(CORPUS), conf["vocab_size"], **conf["calibration"]))
    marks.append(("calibration", time.perf_counter()))
    eng = drive.build_engine(cfg, params, proj, lanes, max_seq, conf, mix)
    marks.append(("engine", time.perf_counter()))
    check_plan(eng.dispatch_plan(), conf["serve"]["backend"], cell.chips)

    planned = traffic.closed_loop(mix, lanes, seed, conf["vocab_size"])
    reqs = drive.requests(planned, mix["temperature"])
    open_after = int(mix["open_after_completions_per_lane"] * lanes)
    hooks = {}
    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        hooks = dict(
            on_open=lambda: jax.profiler.start_trace(
                trace_dir, profiler_options=opts),
            on_close=jax.profiler.stop_trace,
            annotate=lambda i: jax.profiler.TraceAnnotation(trace.SPAN, i=i))
    win = drive.drive(eng, reqs, seconds=seconds,
                      open_after_completions=open_after,
                      clock=time.perf_counter, **hooks)
    marks.append(("first wave", win.t_open))
    steps = [f"{n} {t - p:.1f}s" for (n, t), (_, p)
             in zip(marks, [(None, t_start)] + marks)]
    print(f"chipbench: setup phases: {', '.join(steps)}", file=sys.stderr)
    peaks = device_peaks(devices)
    peak = fullest(peaks)
    fifo = win.admission_order == list(range(len(win.admission_order)))
    events = eng.mesh_fallback_events()
    eng.last_state = eng.last_lanes = None
    del eng
    gc.collect()

    rec = None
    if traced:
        rec = trace.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    prompts = {p.uid: p.tokens for p in planned}
    sample = check.pick(win.served, win.finished, cp["check"], seed)
    readings = check.compare(arch, conf, params, proj, prompts, win.served,
                             sample, max_seq, control=control)
    print(f"chipbench: compared {readings.tokens} served tokens of requests "
          f"{readings.requests} in {readings.seconds:.1f}s: widest gap "
          f"{readings.number('widest_gap')!r}, mean gap "
          f"{readings.number('mean_gap')!r}", file=sys.stderr)
    ok, shown = check.verdict(readings, cp["check"]["limits"])
    shown["fifo_admissions"] = {"value": int(fifo), "limit": 1}
    shown["kernel_fallbacks"] = {"value": len(events), "limit": 0}
    print(f"chipbench check: fifo_admissions {int(fifo)} limit 1",
          file=sys.stderr)
    print(f"chipbench check: kernel_fallbacks {len(events)} limit 0",
          file=sys.stderr)
    ok = ok and fifo and not events

    run = Run(lanes=lanes, chips=cell.chips, shapes=arch.shapes(conf),
              peaks=chip_peaks(dev.device_kind) if require_tpu
              else chip_peaks("TPU v5 lite"),
              window=win, prompt_len={p.uid: len(p.tokens) for p in planned},
              setup_s=win.t_open - t_start,
              compiles_in_window=clock.count_between(win.t_open, win.t_close),
              memory_peak_bytes=peak, trace=rec)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak,
              "memory_peak_bytes_per_device": peaks}
    served_in_window = {uid for u in win.units for uid, _ in u.tokens}
    result = {"correct": bool(ok), "attempted": len(served_in_window),
              "failed": 0 if ok else len(sample),
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace.busy_seconds(rec)
        device["window_s"] = trace.window_seconds(rec)
        result["breakdown"] = {"device_ops": trace.top_ops(rec),
                               "idle_gaps": trace.idle_gaps(rec, win.labels)}
    print(f"chipbench: setup {run.setup_s:.1f}s "
          f"({clock.seconds_between(t_start, win.t_open):.1f}s compiling), "
          f"window {run.window_s:.2f}s, {drive.window_tokens(win)} tokens, "
          f"{len(win.completion_order)} completions, {clock.seconds:.1f}s "
          f"compiling in all ({clock.cache_hits} cache hits)", file=sys.stderr)
    result["check"] = shown
    if control:
        # the control in the program's place, judged by the same verdict
        c_ok, c_shown = check.verdict(readings, cp["check"]["limits"],
                                      control=True)
        result["control"] = {"correct": bool(c_ok), "check": c_shown}
    if control or keep_readings:
        result["readings"] = readings
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(REPO, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
