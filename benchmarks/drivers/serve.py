"""Serving driver: the program's `ServingEngine` under an open loop of
requests fixed before the run (benchmarks/traffic/requests.py), on the wall
clock. One `engine.run(requests)` call is the window: every request is due
inside `--seconds`, the run drains after the last arrival, and the metrics
are over all of them."""

from __future__ import annotations

import gc
import importlib
import threading
import time

import numpy as np

from benchmarks import compare, counts, tracing
from benchmarks import device as dev
from benchmarks.drivers import lm_adapter
from benchmarks.reference import gpt2
from benchmarks.stats import percentile
from benchmarks.traffic import requests as traffic_requests

PAD_TO = 1024  # reference sequences are padded at the end to a multiple of this


def _trace_thread(window: tracing.TraceWindow, start_after: float, length: float):
    """Start and stop the profiler from one helper thread while the main
    thread is inside `engine.run` (the engine has no hook to do it from)."""
    def body():
        time.sleep(start_after)
        window.start()
        time.sleep(length)
        window.stop()

    thread = threading.Thread(target=body, name="bench-trace", daemon=True)
    thread.start()
    return thread


def served_gaps(cfg: dict, weights: dict, sample: list, n_rows: int) -> list[dict]:
    """For each sampled request (prompt, served tokens): run the reference
    once over the prompt with its served tokens, and at every served
    position read how far the served token's logit lies below the
    reference's best."""
    import jax.numpy as jnp

    out = []
    for prompt, served in sample:
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        first_row = len(prompt) - 1
        length = -(-len(seq) // PAD_TO) * PAD_TO  # few lengths, so few compiled programs
        padded = np.zeros(min(length, cfg["n_positions"]), np.int32)
        padded[:len(seq)] = seq
        start = min(first_row, len(padded) - n_rows)  # keep the slice inside
        rows = slice(first_row - start, first_row - start + len(served))
        logits = gpt2.served_rows_logits(cfg, weights, jnp.asarray(padded), start, n_rows)
        logits = np.asarray(logits, np.float32)[rows]
        gaps = logits.max(axis=-1) - logits[np.arange(len(served)), np.asarray(served)]
        out.append({"prompt_len": len(prompt), "tokens": len(served),
                    "max_gap": float(gaps.max()), "sum_gap": float(gaps.sum()),
                    "agree": int(np.sum(gaps == 0.0))})
    return out


def judge_served(verdict: compare.Verdict, rows: list[dict], limits: dict) -> dict:
    """Two numbers over all sampled tokens: the widest gap (a token altered
    where it is produced opens one of the logits' whole spread) and the mean
    gap (what a lower precision moves: it flips more near-ties, by more)."""
    tokens = sum(r["tokens"] for r in rows)
    note = (f"of {tokens} served tokens in {len(rows)} requests, longest "
            f"{max((r['prompt_len'] + r['tokens'] for r in rows), default=0)}")
    widest = max((r["max_gap"] for r in rows), default=float("inf"))
    mean = sum(r["sum_gap"] for r in rows) / tokens if tokens else float("inf")
    verdict.add("served_token_gap", widest, limits["served_token_gap"], "widest " + note)
    verdict.add("served_mean_gap", mean, limits["served_mean_gap"], "mean " + note)
    return {"tokens_checked": tokens,
            "agree_with_reference": sum(r["agree"] for r in rows) / tokens if tokens else None}


def pick_sample(finished: list, seed: int, n: int) -> list:
    """A seeded sample of finished requests, the longest always in it."""
    finished = sorted(finished, key=lambda r: r[0])
    longest = max(finished, key=lambda r: (len(r[1]) + len(r[2]), -r[0]))
    rng = np.random.default_rng(seed)
    others = [r for r in finished if r[0] != longest[0]]
    idx = rng.choice(len(others), size=min(n - 1, len(others)), replace=False)
    return [(p, t) for _, p, t in [longest] + [others[i] for i in sorted(idx)]]


def make_requests(traffic: dict, cfg: dict, seed: int, seconds: float) -> list:
    """The window's requests, from the generator the traffic file names
    (`benchmarks/traffic/<generator>.py`)."""
    generator = importlib.import_module(f"benchmarks.traffic.{traffic['generator']}")
    return generator.make(traffic, cfg, seed, seconds)


def build_engine(cell, seed: int, **serve_config):
    """The program's engine on weights from the seed. ``serve_config``
    overrides fields of the cell's (tools/control.py: the engine's own lower
    precisions, `check.controls`)."""
    import jax
    from tpudml.serve.engine import ServeConfig, ServingEngine

    cfg, spec = cell.config, cell.spec
    model = lm_adapter.build_model(cfg, spec["model"])
    dtype = lm_adapter.param_dtype(spec["model"])
    params = jax.jit(lambda key: lm_adapter.to_program(
        gpt2.init_weights(cfg, key, dtype), cfg["n_layer"]))(gpt2.seed_key(seed))
    return ServingEngine(model, params, ServeConfig(
        **{**spec["engine"]["serve_config"], **serve_config}))


def warm_up(engine, cell, seed: int) -> None:
    """The cell's warm-up requests: they touch every program the traffic
    can reach (each prefill-chunk offset, the decode step)."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    vocab = cell.config["vocab_size"]
    engine.run([
        traffic_requests.Request(
            rid=i, prompt=rng.integers(0, vocab, w["prompt_len"]).astype(np.int32),
            max_new_tokens=w["max_new_tokens"], arrival_time=0.0)
        for i, w in enumerate(cell.spec["warmup"])])


def run(cell, seed: int, seconds: float, trace: bool, devices, started: float,
        trace_dir: str) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, spec, traffic = cell.config, cell.spec, cell.traffic
    check = spec["check"]
    engine = build_engine(cell, seed)
    warm_up(engine, cell, seed)
    reqs = make_requests(traffic, cfg, seed, seconds)
    gc.collect()

    window = tracing.TraceWindow(trace_dir) if trace else None
    tracer = None
    if window is not None:
        at = spec["trace"]
        start = min(at["start_s"], max(0.0, seconds - at["seconds"]) / 2)
        tracer = _trace_thread(window, start, min(at["seconds"], seconds))
    t0 = time.perf_counter()
    report = engine.run(reqs)
    elapsed = time.perf_counter() - t0
    if tracer is not None:
        tracer.join()
    setup_s = t0 - started
    memory_peak = dev.memory_peak_bytes(devices)

    stats = [report.requests[r.rid] for r in reqs]
    bad = [s for s in stats if s.finished is None]
    ttft = [(s.first_token if s.first_token is not None else report.wall_time)
            - s.arrival for s in stats]
    tpot = [s.tpot_s for s in stats if s.tpot_s is not None]
    waits = [s.admit_start - s.arrival for s in stats if s.admit_start is not None]
    generated = sum(len(s.tokens) for s in stats)
    # Completed inside the window, on the engine's clock (it starts with the
    # run): the drain after the last arrival belongs to the tails, not here.
    in_window = sum(1 for s in stats for t in s.token_times if t <= seconds)
    serve_cfg = engine.cfg

    # ---- the reference, after the engine's weights and cache are freed
    finished = [(r.rid, r.prompt, list(report.requests[r.rid].tokens))
                for r in reqs if report.requests[r.rid].finished is not None]
    owed = sum(1 for r in reqs if report.requests[r.rid].finished is not None
               and len(report.requests[r.rid].tokens) != r.max_new_tokens)
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    verdict = compare.Verdict()
    verdict.add("requests_not_finished", float(len(bad)), 0.0, f"of {len(reqs)} offered")
    verdict.add("token_count_mismatch", float(owed), 0.0,
                "finished requests whose token count differs from what was asked")
    rows = []
    if finished:
        weights = jax.jit(lambda key: gpt2.init_weights(
            cfg, key, lm_adapter.param_dtype(spec["model"])))(gpt2.seed_key(seed))
        rows = served_gaps(cfg, weights, pick_sample(finished, seed, check["sample"]),
                           traffic["output_len"]["max"])
        del weights
    judged = judge_served(verdict, rows, check["limits"])
    reference_s = time.perf_counter() - t_ref

    return {
        "verdict": verdict, "attempted": len(reqs), "failed": len(bad),
        "end_to_end": {
            "serve.tpot_p95_ms": 1e3 * percentile(tpot, 95) if tpot else float("nan"),
            "serve.tokens_per_s": in_window / seconds,
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "host": {
            "queue_waits_s": waits,
            "ttft_s": ttft,
            "decode_step_bytes": counts.decode_step_bytes(
                cfg, serve_cfg.slots, serve_cfg.max_len,
                weight_bytes=jnp.dtype(lm_adapter.param_dtype(spec["model"])).itemsize,
                cache_bytes={"f32": 4, "bf16": 2, "int8": 1}[serve_cfg.cache_kind]),
        },
        "info": {
            "requests": len(reqs), "generated_tokens": generated,
            "tokens_in_window": in_window,
            "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
            "wall_s": report.wall_time, "drain_s": report.wall_time - seconds,
            "decode_steps": report.decode_steps, "occupancy": report.occupancy,
            "peak_queue_depth": report.peak_queue_depth,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50) if tpot else None,
            "queue_wait_p50_ms": 1e3 * percentile(waits, 50) if waits else None,
            **judged, "reference_s": reference_s, "elapsed_s": elapsed,
        },
    }
