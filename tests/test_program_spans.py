"""The program's own spans (`tpudml.obs.tracer.span`): where
`ServingEngine.run`, `train_loop` and the data path open them, what they
carry, the off position, the way back out of a profiler trace, and the names
on the Pallas kernels.

Load-bearing properties:

- every offered request has exactly one `serve/arrive`, every admitted one
  exactly one `serve/admit` with its `rid`; `active` over `serve/dispatch`
  sums to `ServeReport.busy_slot_steps`; children lie inside their
  `serve/iter`; `arrival <= staged <= admit_start` for every request;
- with the tracer off the same runs give identical tokens / parameters and
  allocate no `Span` (`SPANS_ALLOCATED`);
- one `train/iter` per step with the named children;
- under a `jax.profiler` session the spans come back from the `.xplane.pb`
  with their counters (`benchmarks/program_spans.load`);
- the `pallas_call` equations of every kernel entry point, forward and
  gradient, carry the names the device trace is read by.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudml.core.prng import seed_key
from tpudml.data.datasets import ArrayDataset
from tpudml.data.loader import DataLoader
from tpudml.data.prefetch import prefetch_to_device
from tpudml.models import LeNet, TransformerLM
from tpudml.obs import Tracer, use_tracer
from tpudml.obs import tracer as tracer_mod
from tpudml.optim import make_optimizer
from tpudml.serve import ServeConfig, ServingEngine, poisson_workload
from tpudml.train import train_loop

V = 48


# ------------------------------------------------------------------- serving


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=V, embed_dim=32, num_heads=4, num_layers=2,
                          max_len=64, rope=True, num_kv_heads=2)
    params, _ = model.init(jax.random.key(0))
    return model, params


def _serve(lm, **config):
    model, params = lm
    cfg = ServeConfig(**{"slots": 3, "max_len": 64, "prefill_chunk": 8, **config})
    reqs, _ = poisson_workload(10, 200.0, seed=11, vocab_size=V,
                               prompt_len=(2, 20), new_tokens=(3, 8))
    return reqs, ServingEngine(model, params, cfg).run(reqs)


def _inside(child, parent):
    return (child.tid == parent.tid and parent.ts_us <= child.ts_us
            and child.ts_us + child.dur_us <= parent.ts_us + parent.dur_us)


def _spans(tracer, name):
    cat, _, short = name.partition("/")
    return [s for s in tracer.events if s.cat == cat and s.name == short]


@pytest.fixture(scope="module")
def traced_serve(lm):
    tracer = Tracer()
    with use_tracer(tracer):
        reqs, report = _serve(lm)
    return tracer, reqs, report


def test_serve_every_request_arrives_once_and_is_admitted_once(traced_serve):
    tracer, reqs, report = traced_serve
    arrive, admit = _spans(tracer, "serve/arrive"), _spans(tracer, "serve/admit")
    assert sorted(s.args["rid"] for s in arrive) == sorted(r.rid for r in reqs)
    admitted = [rid for rid, st in report.requests.items() if st.admitted is not None]
    assert sorted(s.args["rid"] for s in admit) == sorted(admitted) == sorted(
        e[1] for e in report.events if e[0] == "admit")
    for s in admit:
        st = report.requests[s.args["rid"]]
        assert s.args["slot"] == st.slot and s.args["prompt_len"] == st.prompt_len
        assert s.args["chunks"] == -(-(st.prompt_len - 1) // 8)
        assert s.args["shared_pages"] == 0
    assert all(s.args["rejected"] == 0 and s.args["late_us"] >= 0 for s in arrive)


def test_serve_dispatch_counters_match_the_report(traced_serve):
    tracer, _, report = traced_serve
    dispatch = _spans(tracer, "serve/dispatch")
    commit = _spans(tracer, "serve/commit")
    assert len(dispatch) == len(commit) == report.decode_steps
    assert len(_spans(tracer, "serve/fetch")) == report.decode_steps
    assert sum(s.args["active"] for s in dispatch) == report.busy_slot_steps
    assert [s.args["step"] for s in dispatch] == list(range(report.decode_steps))
    assert [s.args["step"] for s in commit] == list(range(report.decode_steps))
    assert sum(s.args["tokens"] for s in commit) == report.generated_tokens
    assert sum(s.args["finished"] for s in commit) == len(report.requests)
    assert all(s.args["expired"] == 0 for s in commit)
    # rows: the cache rows that hold a token, under slots x max_len
    assert all(0 < s.args["rows"] <= 3 * 64 for s in dispatch)
    # head_dim 8 does not fill a 128-lane tile: one update per slot
    assert all(s.args["row_scatter"] == 0 for s in dispatch)
    # nor does the Pallas decode-attention kernel read it (and no TPU here)
    assert all(s.args["decode_kernel"] == 0 for s in dispatch)


def test_serve_children_lie_inside_their_pass(traced_serve):
    tracer, _, _ = traced_serve
    passes = _spans(tracer, "serve/iter")
    assert [p.args["step"] for p in passes] == sorted(p.args["step"] for p in passes)
    for name in ("arrive", "admit", "dispatch", "fetch", "commit", "idle"):
        for child in _spans(tracer, f"serve/{name}"):
            holders = [p for p in passes if _inside(child, p)]
            assert len(holders) == 1, (name, child)
            # A pass's `step` is the step it dispatches; with a step kept in
            # flight (a dense engine) it fetches and commits the one before.
            if "step" in (child.args or {}):
                assert child.args["step"] == holders[0].args["step"] - (
                    name in ("fetch", "commit"))
    assert all({"step", "queue", "active"} <= set(p.args) for p in passes)


def test_serve_staged_separates_lateness_from_queueing(traced_serve):
    _, _, report = traced_serve
    for st in report.requests.values():
        assert st.arrival <= st.staged <= st.admit_start <= st.admitted
    lat = report.latency_summary()
    assert 0 <= lat["stage_lateness_p50_s"] <= lat["stage_lateness_p99_s"]
    assert 0 <= lat["queue_wait_p50_s"] <= lat["queue_wait_p99_s"]


def test_serve_rejections_and_shared_pages_ride_on_the_spans(lm):
    tracer = Tracer()
    with use_tracer(tracer):
        _, report = _serve(lm, slots=1, max_queue=2, step_time_s=0.01,
                           cache_layout="paged", page_size=8, prefix_sharing=True)
    arrive = _spans(tracer, "serve/arrive")
    assert report.rejected > 0
    assert sum(s.args["rejected"] for s in arrive) == report.rejected
    assert all(st.staged is not None for st in report.requests.values())
    admit = _spans(tracer, "serve/admit")
    assert [s.args["shared_pages"] for s in admit] == [
        report.requests[s.args["rid"]].shared_pages for s in admit]
    queues = [p.args["queue"] for p in _spans(tracer, "serve/iter")]
    assert max(queues) == 2  # depth after staging never passes max_queue


def test_serve_tracer_off_same_tokens_and_no_span(lm, traced_serve):
    _, _, traced = traced_serve
    before = tracer_mod.SPANS_ALLOCATED
    _, plain = _serve(lm)
    assert tracer_mod.SPANS_ALLOCATED == before
    # Which pass first sees an arrival is wall-clock timing; who is
    # admitted and evicted, and what they are served, is not.
    assert sorted(e[:2] for e in plain.events) == sorted(
        e[:2] for e in traced.events)
    for rid, st in plain.requests.items():
        assert st.tokens == traced.requests[rid].tokens


# ------------------------------------------------------------------ training


def _train(hooks=None, log_every=2):
    rng = np.random.default_rng(3)
    data = ArrayDataset(rng.normal(size=(24, 28, 28, 1)).astype(np.float32),
                        rng.integers(0, 10, size=(24,)).astype(np.int32))

    class Feed:
        """DataLoader behind prefetch_to_device, as the benchmark feeds it."""

        def __init__(self):
            self.loader = DataLoader(data, 4)

        def set_epoch(self, epoch):
            self.loader.set_epoch(epoch)

        def __iter__(self):
            return prefetch_to_device(self.loader, size=2)

    return train_loop(LeNet(), make_optimizer("adam", 1e-3), Feed(), 2, seed_key(0),
                      log_every=log_every, hooks=hooks)


def test_train_loop_one_iter_per_step_with_named_children():
    tracer = Tracer()
    seen = []
    with use_tracer(tracer):
        ts, last = _train(hooks=[lambda *, step, **_: seen.append(step)])
    steps = last["steps"]
    assert steps == 12 == int(ts.step) and seen == list(range(1, 13))
    passes = _spans(tracer, "train/iter")
    stepping = [p for p in passes
                if any(_inside(c, p) for c in _spans(tracer, "train/step"))]
    assert [p.args["step"] for p in stepping] == list(range(1, steps + 1))
    # the other passes: one per epoch, where the loader is found exhausted
    assert len(passes) - len(stepping) == 2
    for name, count in (("train/next_batch", len(passes)), ("train/step", steps),
                        ("train/hooks", steps), ("train/log_sync", steps // 2)):
        children = _spans(tracer, name)
        assert len(children) == count, name
        assert all(sum(_inside(c, p) for p in passes) == 1 for c in children)
    assert [c.args["step"] for c in _spans(tracer, "train/log_sync")] == [2, 4, 6, 8, 10, 12]
    gathers, puts = _spans(tracer, "data/gather"), _spans(tracer, "data/device_put")
    assert len(gathers) == len(puts) == steps
    assert all(g.args["rows"] == 4 for g in gathers)
    assert all(p.args["n_bytes"] == 4 * 28 * 28 * 4 + 4 * 4 for p in puts)
    fetches = _spans(tracer, "train/next_batch")
    assert all(any(_inside(c, f) for f in fetches) for c in gathers + puts)


def test_train_loop_tracer_off_same_parameters_and_no_span():
    tracer = Tracer()
    with use_tracer(tracer):
        traced, _ = _train(log_every=0)
    before = tracer_mod.SPANS_ALLOCATED
    plain, _ = _train(log_every=0)
    assert tracer_mod.SPANS_ALLOCATED == before
    for a, b in zip(jax.tree.leaves(traced.params), jax.tree.leaves(plain.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------- out of a profiler trace again


def test_spans_come_back_from_a_profiler_trace_with_their_counters(lm, tmp_path):
    from benchmarks import program_spans, tracing

    window = tracing.TraceWindow(str(tmp_path))
    window.start()
    try:
        _, report = _serve(lm)
    finally:
        window.stop()
    loaded = program_spans.load(str(tmp_path))
    assert loaded["window"] is not None
    spans = program_spans.inside(loaded)
    assert {s[0] for s in spans} >= {"serve/iter", "serve/arrive", "serve/admit",
                                     "serve/dispatch", "serve/fetch", "serve/commit"}
    dispatch = program_spans.named(spans, "serve/dispatch")
    assert sum(program_spans.stat(dispatch, "active")) == report.busy_slot_steps
    assert program_spans.stat(dispatch, "step") == list(range(report.decode_steps))
    commit = program_spans.named(spans, "serve/commit")
    assert sum(program_spans.stat(commit, "tokens")) == report.generated_tokens
    admits = program_spans.named(spans, "serve/admit")
    assert sorted(program_spans.stat(admits, "rid")) == sorted(report.requests)
    for it in program_spans.named(spans, "serve/iter"):
        assert {"step", "queue", "active"} <= set(it[3])
        for child in program_spans.children(spans, it, "serve/dispatch"):
            assert child[3]["step"] == it[3]["step"]
    assert len(program_spans.loop_host_s(spans)) > 0
    assert program_spans.load(str(tmp_path / "nothing")) is None


# ------------------------------------------------------- names on the kernels


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_names(sub))
    return out


def _flash(q, k, v):
    from tpudml.ops.attention_kernel import flash_attention

    return flash_attention(q, k, v, causal=True, interpret=True)


def _flash_streamed(q, k, v):
    """More tile pairs than the resident forward and the one-pass backward
    unroll: the shape takes the kernels that stream tiles (the forward's,
    the dQ and dK/dV pair), as a head too long for VMEM does."""
    from tpudml.ops.attention_kernel import flash_attention

    return flash_attention(q, k, v, causal=True, interpret=True, block_q=2,
                           block_k=2)


def _ln(x, g, b):
    from tpudml.ops.layernorm_kernel import fused_layernorm

    return fused_layernorm(x, g, b, interpret=True)


def _add_ln(x, g, b):
    from tpudml.ops.layernorm_kernel import fused_add_layernorm

    return fused_add_layernorm(x, x, g, b, interpret=True)


def _xent(save_s):
    def loss(x, w, labels):
        from tpudml.ops.xent_kernel import linear_cross_entropy

        return linear_cross_entropy(x, w, labels, interpret=True, save_s=save_s)
    return loss


_QKV = [jnp.ones((1, 16, 2, 8), jnp.float32)] * 3
_ROWS = [jnp.ones((16, 32), jnp.float32), jnp.ones((32,)), jnp.zeros((32,))]
_HEAD = [jnp.ones((16, 32), jnp.float32), jnp.ones((32, 256), jnp.float32),
         jnp.zeros((16,), jnp.int32)]

KERNELS = [
    ("flash-forward", _flash, _QKV, None, ["flash_fwd_resident"]),
    ("flash-forward-streamed", _flash_streamed, _QKV, None, ["flash_fwd"]),
    ("flash-gradient", _flash, _QKV, (0, 1, 2), ["flash_fwd_resident", "flash_bwd"]),
    ("flash-gradient-streamed", _flash_streamed, _QKV, (0, 1, 2),
     ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    ("ln-forward", _ln, _ROWS, None, ["ln_fwd"]),
    ("ln-gradient", _ln, _ROWS, (0, 1, 2), ["ln_fwd", "ln_bwd"]),
    ("add_ln-forward", _add_ln, _ROWS, None, ["add_ln_fwd"]),
    ("add_ln-gradient", _add_ln, _ROWS, (0, 1, 2), ["add_ln_fwd", "add_ln_bwd"]),
    ("xent-forward", _xent(False), _HEAD, None, ["xent_fwd"]),
    ("xent-gradient", _xent(False), _HEAD, (0, 1),
     ["xent_fwd", "xent_bwd_dx", "xent_bwd_dw"]),
    ("xent_saved-gradient", _xent(True), _HEAD, (0, 1),
     ["xent_fwd_save", "xent_bwd_dx_saved", "xent_bwd_dw_saved"]),
]


@pytest.mark.parametrize("fn,args,argnums,names", [k[1:] for k in KERNELS],
                         ids=[k[0] for k in KERNELS])
def test_pallas_calls_carry_their_kernel_and_pass(fn, args, argnums, names):
    def scalar(*a):
        return sum(jnp.sum(o.astype(jnp.float32)) for o in jax.tree.leaves(fn(*a)))

    target = scalar if argnums is None else jax.grad(scalar, argnums=argnums)
    found = _pallas_names(jax.make_jaxpr(target)(*args).jaxpr)
    assert sorted(found) == sorted(names)
    # benchmarks/tracing.op_family strips trailing digits and dots
    assert all(n and not n[-1].isdigit() and not n.endswith(".") for n in found)
