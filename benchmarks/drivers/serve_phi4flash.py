"""Serving driver for the `phi4flash` reference's model (`tpudml.models.HybridLM`
with Mamba-1 layers, differential attention in window and full layers, gated
memory units and cross layers over the full layer's cache): `drivers/serve_mimo.py`'s
run with this model's adapter, reference and counts. A driver binds its reference
by import, so the run is written out again here; what names no model is imported
from `drivers/serve.py` (the trace thread, the warm-up, the traffic, the sample,
the judgement) and `drivers/serve_hybrid.py` (the longest pass). The model has no
experts, so there are no routes to follow: `correct` is the served tokens' gaps.

The window lies in the steady state behind the cell's `ramp_s`, as
`serve_hybrid.py` sets out. Reference sequences are padded to the first of the
cell's `check.pad_to` that holds them, and the head runs over as many rows as the
longest answer, or the whole of a shorter sequence.

`decode_step_bytes` follows the step's own counters (`counts_phi4flash.py`): the
mean over the decode steps inside the traced window of what each step had to move
(its `rows_read_full`, `rows_window`, `state_bytes` and `active` from
`serve/dispatch`)."""

from __future__ import annotations

import gc
import statistics
import time
from functools import lru_cache

import numpy as np

from benchmarks import compare, counts_phi4flash, tracing
from benchmarks import device as dev
from benchmarks.drivers import phi4flash_adapter
from benchmarks.drivers.serve import (_trace_thread, judge_served, make_requests,  # noqa: F401
                                      pick_sample, warm_up)
from benchmarks.drivers.serve_hybrid import longest_pass
from benchmarks.reference import phi4flash as ref
from benchmarks.stats import percentile

_CACHE_BYTES = {"f32": 4, "bf16": 2}
STEP_COUNTERS = ("active", "rows_full", "rows_read_full", "rows_window", "state_bytes")


@lru_cache(maxsize=1)
def _gaps_program():
    """logits [rows, V], tokens [rows] -> how far each row's token lies below the
    row's best, in one program a shape (a request's own length would be a shape,
    and a compile, of its own)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda logits, tokens: logits.max(axis=-1) - jnp.take_along_axis(
        logits, tokens[:, None], axis=-1)[:, 0])


def served_gaps(cfg: dict, weights: dict, sample: list, n_rows: int, pad_to: list) -> list[dict]:
    """For each sampled request (prompt, served tokens): the reference once over
    the prompt with its served tokens, and at every served position how far the
    served token's logit lies below the reference's best. The logits stay on the
    device (200,064 wide): only the gaps come back."""
    import jax.numpy as jnp

    out = []
    for prompt, served in sample:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        first_row = len(prompt) - 1
        length = next(n for n in pad_to if n >= len(seq))
        rows = min(n_rows, length)
        padded = np.zeros(length, np.int32)
        padded[:len(seq)] = seq
        start = min(first_row, length - rows)  # keep the slice inside
        tokens = np.zeros(rows, np.int32)
        mine = slice(first_row - start, first_row - start + len(served))
        tokens[mine] = served
        logits = ref.served_rows_logits(cfg, weights, jnp.asarray(padded), start, rows)
        gaps = np.asarray(_gaps_program()(logits, jnp.asarray(tokens)), np.float32)[mine]
        out.append({"prompt_len": len(prompt), "tokens": len(served),
                    "max_gap": float(gaps.max()), "sum_gap": float(gaps.sum()),
                    "agree": int(np.sum(gaps == 0.0))})
    return out


def finished_requests(reqs: list, report) -> list:
    """(rid, prompt, served tokens) of every request that finished."""
    return [(r.rid, r.prompt, list(s.tokens))
            for r in reqs for s in [report.requests[r.rid]] if s.finished is not None]


def make_weights(cell, seed: int):
    """The reference's flat weights from the seed, in the cell's stored type."""
    import jax

    dtype = phi4flash_adapter.param_dtype(cell.spec["model"])
    return jax.jit(lambda key: ref.init_weights(cell.config, key, dtype))(ref.seed_key(seed))


def make_params(cell, seed: int):
    """The same weights as the program's parameter tree, in one program."""
    import jax

    cfg = cell.config
    dtype = phi4flash_adapter.param_dtype(cell.spec["model"])
    return jax.jit(lambda key: phi4flash_adapter.to_program(
        ref.init_weights(cfg, key, dtype), cfg))(ref.seed_key(seed))


def build_engine(cell, seed: int, **model_options):
    """The program's engine on weights from the seed. ``model_options``
    override the cell's (tools/control_phi4flash.py: the `model` options of a
    `check.controls` entry)."""
    from tpudml.serve.engine import ServeConfig, ServingEngine

    model = phi4flash_adapter.build_model(cell.config, {**cell.spec["model"], **model_options})
    return ServingEngine(model, make_params(cell, seed),
                         ServeConfig(**cell.spec["engine"]["serve_config"]))


def step_counters(events: list, lo_us: float, hi_us: float) -> list[dict]:
    """The `serve/dispatch` counters of the decode steps that start in
    [lo_us, hi_us) of the tracer's clock and carry the shared cache's counters."""
    return [e.args for e in events
            if e.cat == "serve" and e.name == "dispatch" and lo_us <= e.ts_us < hi_us
            and "rows_read_full" in (e.args or {})]


def _stored(spec: dict) -> dict:
    import jax.numpy as jnp

    return dict(weight_bytes=jnp.dtype(phi4flash_adapter.param_dtype(spec["model"])).itemsize,
                cache_bytes=_CACHE_BYTES[spec["engine"]["serve_config"]["cache_kind"]])


def step_bytes(cfg: dict, spec: dict, step: dict) -> float:
    """`counts_phi4flash.decode_step_bytes` of one step's counters."""
    return counts_phi4flash.decode_step_bytes(
        cfg, step["rows_read_full"], step["rows_window"], step["state_bytes"], step["active"],
        **_stored(spec))


def shared_bytes(cfg: dict, spec: dict, step: dict) -> float:
    """Of `step_bytes`, the cross layers' re-reads of the full layer's cache."""
    return counts_phi4flash.shared_read_bytes(cfg, step["rows_full"], step["rows_read_full"],
                                              _stored(spec)["cache_bytes"])


def run(cell, seed: int, seconds: float, trace: bool, devices, started: float,
        trace_dir: str) -> dict:
    from tpudml.obs.tracer import Tracer, use_tracer

    cfg, spec, traffic = cell.config, cell.spec, cell.traffic
    check = spec["check"]
    ramp = float(spec["ramp_s"])  # served before the window opens: set-up
    engine = build_engine(cell, seed)
    warm_up(engine, cell, seed)
    offered = make_requests(traffic, cfg, seed, ramp + seconds)
    reqs = [r for r in offered if r.arrival_time >= ramp]  # the window's
    gc.collect()

    window = tracing.TraceWindow(trace_dir) if trace else None
    tracer, start, length = None, 0.0, 0.0
    if window is not None:
        at = spec["trace"]
        start = ramp + min(at["start_s"], max(0.0, seconds - at["seconds"]) / 2)
        length = min(at["seconds"], seconds)
        tracer = _trace_thread(window, start, length)
    recorder = Tracer()  # every run: a few microseconds a span, ten spans a pass
    t_run = time.perf_counter()
    with use_tracer(recorder):
        report = engine.run(offered)
    elapsed = time.perf_counter() - t_run
    if tracer is not None:
        tracer.join()
    setup_s = t_run + ramp - started
    memory_peak = dev.memory_peak_bytes(devices)
    steps = []
    if window is not None:
        lo = (t_run - recorder._t0 + start) * 1e6  # the traced span on the recorder's clock
        steps = step_counters(recorder.events, lo, lo + length * 1e6)
    mean_bytes = statistics.mean(step_bytes(cfg, spec, s) for s in steps) if steps else None

    stats = [report.requests[r.rid] for r in reqs]
    bad = [s for s in report.requests.values() if s.finished is None]
    ttft = [(s.first_token if s.first_token is not None else report.wall_time)
            - s.arrival for s in stats]
    tpot = [s.tpot_s for s in stats if s.tpot_s is not None]
    waits = [s.admit_start - s.arrival for s in stats if s.admit_start is not None]
    generated = sum(len(s.tokens) for s in report.requests.values())
    # Completed inside the window, on the engine's clock (it starts with the
    # run), whoever asked: the ramp's answers that end in the window count, the
    # drain after the last arrival belongs to the tails, not here.
    in_window = sum(1 for s in report.requests.values() for t in s.token_times
                    if ramp < t <= ramp + seconds)

    # ---- the reference, after the engine's weights and cache are freed
    finished = finished_requests(reqs, report)
    owed = sum(1 for r in offered if report.requests[r.rid].finished is not None
               and len(report.requests[r.rid].tokens) != r.max_new_tokens)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    verdict = compare.Verdict()
    verdict.add("requests_not_finished", float(len(bad)), 0.0,
                f"of {len(offered)} offered, {len(reqs)} of them in the window")
    verdict.add("token_count_mismatch", float(owed), 0.0,
                "finished requests whose token count differs from what was asked")
    rows = []
    if finished:
        weights = make_weights(cell, seed)
        rows = served_gaps(cfg, weights, pick_sample(finished, seed, check["sample"]),
                           traffic["output_len"]["max"], check["pad_to"])
        del weights
    judged = judge_served(verdict, rows, check["limits"])
    reference_s = time.perf_counter() - t_ref

    return {
        "verdict": verdict, "attempted": len(offered), "failed": len(bad),
        "end_to_end": {
            "serve.tokens_per_s": in_window / seconds,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "host": {"queue_waits_s": waits, "ttft_s": ttft, "tpot_s": tpot,
                 "decode_step_bytes": mean_bytes,
                 # the traced window's decode steps, by their own counters (means)
                 **{f"decode_{key}": statistics.mean(s[key] for s in steps) if steps else None
                    for key in STEP_COUNTERS}},
        "info": {
            "requests": len(reqs), "generated_tokens": generated,
            "tokens_in_window": in_window,
            "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
            "ramp_s": ramp, "requests_offered": len(offered),
            "wall_s": report.wall_time, "drain_s": report.wall_time - ramp - seconds,
            "decode_steps": report.decode_steps, "occupancy": report.occupancy,
            "peak_queue_depth": report.peak_queue_depth,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50) if tpot else None,
            "tpot_p95_ms": 1e3 * percentile(tpot, 95) if tpot else None,
            "queue_wait_p50_ms": 1e3 * percentile(waits, 50) if waits else None,
            "decode_step_bytes": mean_bytes, "longest_pass": longest_pass(recorder.events),
            **judged, "reference_s": reference_s, "elapsed_s": elapsed,
        },
    }
