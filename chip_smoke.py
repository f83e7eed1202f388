"""Serve the pallas path on one TPU chip through ``repro.serve.Server``.

    python chip_smoke.py [--seed 0]

One process, no child processes, no network, no downloaded files: every
input stream derives from ``--seed``. Phases, each on its own
``Engine(backend="pallas")`` behind a wall-clock ``Server``:

  * ``paper@64``  — the paper mix (relu, vadd, fft, mac1, axpby_ms; the
    div_loop class must be skipped by name) at stream length 64, every
    response checked bit-exact against ``Engine(backend="sim")``;
  * ``model@64``  — the six pallas-eligible model-layer classes of
    ``repro.workloads`` at length 64 (the two SSM classes must be skipped
    with their registered reasons), checked bit-exact against each class's
    ``jnp`` oracle;
  * ``yi-9b``     — the same model classes at the published widths of
    Yi-9B (``repro/configs/yi_9b.py``): d_model 4096 for ln_affine, silu_q
    and moe_gate, d_ff 11008 for swiglu_ms, head_dim 128 for attn_score,
    one 4096-token context row for softmax_den.

Per class (32 requests at length 64, 5 at Yi-9B widths), one request is
served alone (cold: the first kernel compile and timing simulation of
that shape) and the rest are submitted at once, so single-shot classes
ride lane-batched grids. Multi-shot plans (axpby_ms, swiglu_ms) run shot
by shot and never lane-batch.

The run fails (exit 1, no result line) on a missing TPU, interpret mode,
any failed or rejected ticket, any oracle mismatch, any lane grid that fell
back to per-request dispatch, a single-shot class without a lane batch, or
any phase exception. Earlier lines carry host wall-clock times on this
chip — set-up and smoke figures, not benchmark metrics. The last line is
one JSON object: ``{"ok": true, "device": {...}}``.

``--allow-cpu`` is for rehearsing the script without a chip only: it
accepts the CPU backend, where the kernels run in interpret mode (about
half a minute).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs under /tmp
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Yi-9B's pretraining context (arXiv:2403.04652): one softmax row
YI_CONTEXT = 4096
REQUESTS = 32           # per class at length 64: four full lane batches
WIDE_REQUESTS = 5       # per class at Yi-9B widths: one lane batch of 4


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: accept the CPU (interpret mode)")
    return ap.parse_args(argv)


def _wait(tickets, timeout_s=600.0):
    """Block until every ticket completed; a failed or rejected ticket is
    judged by its status afterwards. Outputs are host numpy arrays, so a
    completed ticket means the device work behind it has finished."""
    for tk in tickets:
        try:
            tk.result(timeout=timeout_s)
        except Exception:
            pass


def _serve_class(server, engine, label, art, length, n, rng, check, errors):
    """Serve ``n`` seeded requests of one class: one alone (cold), then
    ``n - 1`` submitted together (lane-batched). Returns a report line."""
    from repro.serve import request_inputs
    from repro.serve.loop import DONE

    inputs = [request_inputs(art, length, rng, label=label)
              for _ in range(n)]
    st = engine.stats
    lanes0 = st.lane_batches
    t0 = time.perf_counter()
    tickets = [server.submit(art, inputs[0])]
    _wait(tickets)
    cold_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    tickets += [server.submit(art, ins) for ins in inputs[1:]]
    _wait(tickets[1:])
    warm_s = time.perf_counter() - t1
    lane_batches = st.lane_batches - lanes0

    bad = [tk for tk in tickets if tk.status != DONE]
    for tk in bad:
        errors.append(f"{label}@{length}: request {tk.rid} {tk.status}: "
                      f"{tk.error!r}")
    mismatches = 0
    for tk, ins in zip(tickets, inputs):
        if tk.status == DONE and not check(label, length, ins, tk.outputs):
            mismatches += 1
    if mismatches:
        errors.append(f"{label}@{length}: {mismatches}/{n} responses differ "
                      f"from the reference")
    if art.n_shots == 1 and n > 2 and lane_batches == 0:
        errors.append(f"{label}@{length}: no lane-batched grid served it")
    warm_ms = 1e3 * warm_s / max(n - 1, 1)
    return (f"  {label:12s} len={length:6d} shots={art.n_shots} n={n:3d} "
            f"cold_s={cold_s:.3f} warm_ms_per_request={warm_ms:.3f} "
            f"lane_batches={lane_batches} failed={len(bad)} "
            f"mismatches={mismatches}")


def _run_phase(name, build, n, rng, check, errors):
    """Compile a phase's classes on a fresh pallas engine, serve them
    class by class through one wall-clock Server, and hold the engine and
    the server report to the smoke contract."""
    from repro.engine import Engine
    from repro.engine.cache import ArtifactCache
    from repro.serve import Server

    t0 = time.perf_counter()
    engine = Engine(backend="pallas", cache=ArtifactCache(memory_only=True))
    classes = build(engine)            # {label: (artifact, length)}
    setup_s = time.perf_counter() - t0
    print(f"[{name}] {len(classes)} classes compiled for the fabric in "
          f"{setup_s:.3f} s (host wall clock)", flush=True)
    before = len(errors)
    t1 = time.perf_counter()
    with Server(engine) as server:
        for label, (art, length) in classes.items():
            print(_serve_class(server, engine, label, art, length, n, rng,
                               check, errors), flush=True)
        report = server.stop(timeout=600)
    serve_s = time.perf_counter() - t1
    st = engine.stats
    print(f"[{name}] served={report['served']} failed={report['failed']} "
          f"rejected={report['rejected']} lane_batches={st.lane_batches} "
          f"lane_requests={st.lane_requests} "
          f"lane_batch_failures={st.lane_batch_failures} "
          f"serve_s={serve_s:.3f} (host wall clock, compiles included)",
          flush=True)
    if report["failed"] or report["rejected"]:
        errors.append(f"{name}: {report['failed']} failed, "
                      f"{report['rejected']} rejected")
    if report["served"] != len(classes) * n:
        errors.append(f"{name}: served {report['served']} of "
                      f"{len(classes) * n}")
    if st.lane_batch_failures:
        errors.append(f"{name}: {st.lane_batch_failures} lane grid(s) fell "
                      f"back to per-request dispatch; last error: "
                      f"{engine.last_lane_error}")
    if st.lane_batches == 0:
        errors.append(f"{name}: no lane-batched grid ran")
    return len(errors) == before


def main(argv=None) -> int:
    args = _parse(argv)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              f"fall back", file=sys.stderr)
        return 1

    from repro.kernels.fabric_reduce import default_interpret, \
        use_compile_cache
    interpret = default_interpret()
    if interpret and not args.allow_cpu:
        print("chip_smoke: kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    print(f"interpret={interpret} compile_cache={use_compile_cache()}",
          flush=True)

    from repro.configs.yi_9b import CFG as YI
    from repro.engine import Engine
    from repro.engine.cache import ArtifactCache
    from repro.serve import compile_recipe, mix_recipes, serve_classes
    from repro.serve.load import class_recipes
    from repro.workloads import MODEL_CLASSES

    sim = Engine(backend="sim", cache=ArtifactCache(memory_only=True))
    sim_arts = {}

    def check_paper(label, length, ins, outs):
        key = (label, length)
        if key not in sim_arts:
            sim_arts[key] = compile_recipe(sim, label, length,
                                           class_recipes(length))
        want = sim.run(sim_arts[key], ins)
        return sorted(outs) == sorted(want) and all(
            np.array_equal(np.asarray(outs[o]), np.asarray(want[o]))
            for o in want)

    def check_model(label, length, ins, outs):
        want = MODEL_CLASSES[label].oracle(**ins)
        return len(outs) == len(want) and all(
            np.array_equal(np.ravel(np.asarray(outs[f"out{i}"])),
                           np.ravel(np.asarray(w)))
            for i, w in enumerate(want))

    errors = []

    def expect_skips(name, skipped, want):
        if skipped != want:
            errors.append(f"{name}: skipped {skipped}, expected {want}")

    def paper64(engine):
        skipped = {}
        arts = serve_classes(engine, 64, skipped=skipped)
        expect_skips("paper@64", set(skipped), {"div_loop"})
        return {label: (a, 64) for label, a in arts.items()}

    model_skips = {label: wc.pallas_skip
                   for label, wc in MODEL_CLASSES.items() if wc.pallas_skip}

    def model64(engine):
        skipped = {}
        arts = serve_classes(engine, 64, mix="model", skipped=skipped)
        expect_skips("model@64", skipped, model_skips)
        return {label: (a, 64) for label, a in arts.items()}

    yi_widths = {"ln_affine": YI.d_model, "silu_q": YI.d_model,
                 "moe_gate": YI.d_model, "swiglu_ms": YI.d_ff,
                 "attn_score": YI.head_dim, "softmax_den": YI_CONTEXT}

    def yi9b(engine):
        return {label: (compile_recipe(engine, label, width,
                                       mix_recipes(width, "model")), width)
                for label, width in yi_widths.items()}

    rng = np.random.default_rng(args.seed)
    phases = [("paper@64", paper64, REQUESTS, check_paper),
              ("model@64", model64, REQUESTS, check_model),
              ("yi-9b", yi9b, WIDE_REQUESTS, check_model)]
    passed = {}
    for name, build, n, check in phases:
        try:
            passed[name] = _run_phase(name, build, n, rng, check, errors)
        except Exception as e:  # a phase failure must not hide the others
            traceback.print_exc()
            errors.append(f"{name}: {type(e).__name__}: {e}")
            passed[name] = False
        print(f"[{name}] {'PASS' if passed[name] else 'FAIL'}", flush=True)

    if errors:
        for e in errors:
            print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
