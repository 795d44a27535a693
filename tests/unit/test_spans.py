"""The program's span and counter recorder (``deepspeed_tpu/utils/spans.py``) and the
spans the engine's main path records with it (docs/telemetry.md)."""

import contextlib
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.utils import spans
from deepspeed_tpu.utils.hlo import instruction_count, optimized_hlo
from simple_model import SimpleModel, random_dataset, simple_config

BACKEND = "/jax/core/compile/backend_compile_duration"


def test_nesting_parents_engine_and_step_are_inherited():
    rec = spans.Recorder()
    eid = rec.new_engine()
    step = rec.begin("train.step", engine=eid, step=7, root=True)
    with rec.span("train.grad_program", program="loss_and_grad") as grad:
        assert rec._stack() == [step, grad]
        with rec.span("inner"):
            pass
    rec.end(step)
    got = {s["name"]: s for s in rec.spans()}
    assert [s["name"] for s in rec.spans()] == ["inner", "train.grad_program", "train.step"]
    assert got["train.step"]["parent"] is None
    assert got["train.grad_program"]["parent"] == got["train.step"]["id"]
    assert got["inner"]["parent"] == got["train.grad_program"]["id"]
    assert {s["engine"] for s in got.values()} == {eid} and {s["step"] for s in got.values()} == {7}
    assert got["train.grad_program"]["attrs"] == {"program": "loss_and_grad"}
    assert all(s["end"] >= s["start"] for s in got.values())
    assert rec._stack() == []
    assert rec.spans(engine=eid + 1) == []


def test_a_root_span_has_no_parent_and_end_closes_what_was_left_open():
    rec = spans.Recorder()
    stale = rec.begin("train.step", engine=1, root=True)
    rec.begin("train.grad_program")                 # never closed: its call raised
    fresh = rec.begin("train.step", engine=2, root=True)
    rec.end(fresh)
    rec.end(stale)
    rec.end(stale)                                  # a second end is nothing
    got = rec.spans()
    assert [(s["name"], s["engine"], s["parent"]) for s in got] == [
        ("train.step", 2, None), ("train.grad_program", 1, stale.id), ("train.step", 1, None)]
    assert rec._stack() == []


def test_two_engines_taking_turns_on_one_thread_keep_their_own_steps():
    rec = spans.Recorder()
    a, b = rec.new_engine(), rec.new_engine()
    step_a = rec.begin("train.step", engine=a, step=0, root=True)
    step_b = rec.begin("train.step", engine=b, step=0, root=True)
    with rec.span("train.grad_program", engine=b, program="loss_and_grad") as grad_b:
        pass
    with rec.span("train.update_program", engine=a, program="apply_update") as update_a:
        assert update_a.parent == step_a.id          # not the span on top, which is b's
        rec.on_compile_event(BACKEND, 0.01)
        rec.begin("left.open")                       # no engine: under the span on top
    rec.end(step_a)
    assert rec._stack() == [step_b]                  # a's end leaves b's step open
    with rec.span("train.update_program", engine=b, program="apply_update") as update_b:
        pass
    rec.end(step_b)
    assert grad_b.parent == update_b.parent == step_b.id
    got = rec.spans()
    assert [(s["name"], s["engine"]) for s in got] == [
        ("train.grad_program", b), ("compile.backend", a), ("left.open", a),
        ("train.update_program", a), ("train.step", a), ("train.update_program", b),
        ("train.step", b)]
    assert rec.counters(a) == {"program.builds[apply_update]": 1} and rec.counters(b) == {}
    assert rec._stack() == []


def test_ring_eviction_leaves_counters_whole():
    rec = spans.Recorder(capacity=8)
    eid = rec.new_engine()
    for n in range(50):
        with rec.span("train.step", engine=eid, step=n):
            rec.count(eid, "program.builds[x]")
            if n % 2:
                rec.count(eid, "program.builds[y]")
    kept = rec.spans()
    assert len(kept) == 8 and [s["step"] for s in kept] == list(range(42, 50))
    assert rec.counters(eid) == {"program.builds[x]": 50, "program.builds[y]": 25}
    assert rec.counters(eid + 1) == {}


def test_a_compile_event_lands_under_the_open_program_span():
    rec = spans.Recorder()
    eid = rec.new_engine()
    with rec.span("train.step", engine=eid, step=0, root=True):
        with rec.span("train.grad_program", program="loss_and_grad") as grad:
            rec.on_compile_event("/jax/core/compile/jaxpr_trace_duration", 1e-5, fun_name="add")
            rec.on_compile_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.2)
            rec.on_compile_event(BACKEND, 0.25, fun_name="jit(loss_and_grad)")
            rec.on_compile_event("/jax/some/other_event", 3.0)
        with rec.span("train.update_program", program="apply_update") as update:
            pass
        rec.on_compile_event(BACKEND, 0.01, fun_name="jit(convert_element_type)")
    got = rec.spans()
    by_name = {}
    for s in got:
        by_name.setdefault(s["name"], []).append(s)
    # the inner jit of a trace is too short to keep; the unknown event is no span
    assert sorted(by_name) == ["compile.backend", "compile.cache_load", "train.grad_program",
                               "train.step", "train.update_program"]
    load, = by_name["compile.cache_load"]
    in_program, under_step = sorted(by_name["compile.backend"], key=lambda s: s["start"])
    assert load["parent"] == in_program["parent"] == grad.id
    assert in_program["attrs"] == {"fun_name": "jit(loss_and_grad)"}
    assert in_program["end"] - in_program["start"] == pytest.approx(0.25)
    assert under_step["parent"] == by_name["train.step"][0]["id"]
    assert grad.attrs["builds"] == 1 and "builds" not in update.attrs
    assert rec.counters(eid) == {"program.builds[loss_and_grad]": 1}


def test_the_process_recorder_is_one_and_hears_jax_compile():
    rec = spans.recorder()
    assert spans.recorder() is rec
    eid = rec.new_engine()
    x = jnp.arange(7.0)                      # an eager operation is a program of its own
    with rec.span("train.grad_program", engine=eid, program="probe"):
        jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    assert rec.counters(eid) == {"program.builds[probe]": 1}


# ------------------------------------------------------------------ the engine
def _children(spans_):
    out = {}
    for s in spans_:
        out.setdefault(s["parent"], []).append(s)
    return out


def _gpt2_engine():
    model = GPT2Model(GPT2Config(vocab_size=128, n_positions=32, n_embd=32, n_layer=2, n_head=2,
                                 use_flash_attention=True, loss_chunk=16,
                                 compute_dtype=jnp.bfloat16))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params={"train_batch_size": 8, "bf16": {"enabled": True},
                       "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                       "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 9})
    tokens = np.random.default_rng(0).integers(0, 128, (8, 32)).astype(np.int32)
    return engine, tokens


def _program_hlos(engine, tokens):
    args = (engine.params, engine.scaler_state.cur_scale, *engine.shard_batch((tokens, tokens)))
    grad = optimized_hlo(engine._jit_loss_and_grad, *args)
    _, grads = engine._jit_loss_and_grad(*args)
    update = optimized_hlo(engine._jit_apply_update, engine.master_params, engine.opt_state,
                           engine.scaler_state, grads, engine.params,
                           jnp.asarray(1, jnp.int32), engine.optimizer.current_hyper())
    return grad, update


def test_three_steps_give_three_roots_with_their_children():
    # another file's test in this worker may have left a window open (a forward() that
    # never reached step()): this engine must add nothing to what was open before it
    open_before = list(spans.recorder()._stack())
    engine, tokens = _gpt2_engine()
    for _ in range(3):
        loss = engine(tokens, tokens)
        engine.backward(loss)
        engine.step()
    rec = spans.recorder()
    eid = engine._span_engine
    mine = rec.spans(engine=eid)
    steps = [s for s in mine if s["name"] == "train.step"]
    assert [s["step"] for s in steps] == [0, 1, 2] and all(s["parent"] is None for s in steps)
    tree = _children(mine)
    for step in steps:
        kids = [c for c in tree[step["id"]] if c["name"].startswith("train.")]
        assert [c["name"] for c in sorted(kids, key=lambda c: c["start"])] == [
            "train.put_batch", "train.grad_program", "train.accumulate", "train.update_program"]
        assert all(step["start"] <= c["start"] and c["end"] <= step["end"] for c in kids)
        programs = {c["name"]: c["attrs"].get("program") for c in kids}
        assert programs["train.grad_program"] == "loss_and_grad"
        assert programs["train.update_program"] == "apply_update"
    # the first step built both programs, under the call that asked for each
    first = {c["name"]: c for c in tree[steps[0]["id"]]}
    for name in ("train.grad_program", "train.update_program"):
        assert first[name]["attrs"]["builds"] >= 1
        assert any(c["name"] == "compile.backend" for c in tree[first[name]["id"]])
    last = {c["name"]: c for c in tree[steps[2]["id"]]}
    assert "builds" not in last["train.grad_program"]["attrs"]
    assert not any(s["name"] == "train.host_fetch" for s in mine)   # bf16, no monitor
    counters = rec.counters(eid)
    # the builds, and what the gradient program's attention calls left while it was traced (PR 60)
    flash = {name for name in counters if name.startswith("flash.")}
    assert set(counters) - flash == {"program.builds[loss_and_grad]", "program.builds[apply_update]"}
    assert all("[loss_and_grad] " in name for name in flash)
    assert min(counters.values()) >= 1
    assert engine._step_span is None and rec._stack() == open_before
    # the catalog, on request: every instruction of each program, scope paths where JAX gave one
    catalog = rec.programs(eid)
    assert set(catalog) == {"loss_and_grad", "apply_update"}
    grad_ops = catalog["loss_and_grad"]["ops"]
    assert catalog["loss_and_grad"]["module"].startswith("jit_")
    paths = " ".join(grad_ops.values())
    for scope in ("ds_embed", "ds_attn", "ds_mlp", "ds_loss", "ds_flash_fwd", "ds_flash_bwd_dkv",
                  "transpose(jvp(ds_mlp))"):
        assert scope in paths, scope
    assert "ds_flash_bwd_dq" not in paths     # the backward is one kernel, one pass
    assert "ds_apply_update" in " ".join(catalog["apply_update"]["ops"].values())
    assert rec.programs(eid) is not catalog and rec.programs(eid) == catalog   # kept, not rebuilt
    # the engine holds its programs and the recorder only refers to them: they go with it
    kept = weakref.ref(engine._step_programs)
    del engine, catalog
    gc.collect()
    assert kept() is None and rec.programs(eid) == {}
    assert len(rec.spans(engine=eid)) == len(mine)      # the spans stay in the ring


def test_fp16_overflow_fetch_is_one_host_fetch_span_a_step():
    model = SimpleModel(16)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(fp16={"enabled": True, "initial_scale_power": 4}))
    data = random_dataset(8, 16)
    xs, ys = np.stack([d[0] for d in data]), np.stack([d[1] for d in data])
    for scale in (1.0, np.inf):
        loss = engine((xs * scale).astype(np.float16), ys.astype(np.float16))
        engine.backward(loss)
        engine.step()
    rec = spans.recorder()
    mine = rec.spans(engine=engine._span_engine)
    tree = _children(mine)
    steps = [s for s in mine if s["name"] == "train.step"]
    assert len(steps) == 2
    for step in steps:
        assert [c["name"] for c in tree[step["id"]] if c["name"] == "train.host_fetch"] == [
            "train.host_fetch"]
    assert engine.skipped_steps == 1 and [s["step"] for s in steps] == [0, 1]


def test_scopes_and_spans_add_no_instruction_to_either_program(monkeypatch):
    engine, tokens = _gpt2_engine()
    with_scopes = _program_hlos(engine, tokens)
    assert "ds_mlp" in with_scopes[0] and "ds_flash_fwd" in with_scopes[0]
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare_engine, _ = _gpt2_engine()
    bare = _program_hlos(bare_engine, tokens)
    assert "ds_mlp" not in bare[0] and "ds_fwd_bwd" not in bare[0]
    for a, b in zip(with_scopes, bare):
        assert instruction_count(a) == instruction_count(b) > 0


# ------------------------------------------- CPU beside wall, the lead, the memory
def _spin(seconds):
    """Burn ``seconds`` of this thread's CPU."""
    until = spans.cpu_clock() + seconds
    while spans.cpu_clock() < until:
        pass


def _wall(span):
    return span["end"] - span["start"]


@pytest.mark.parametrize("work", ["sleeps", "spins"])
def test_a_spans_cpu_seconds_tell_work_from_being_held(work):
    import time
    rec = spans.Recorder()
    with rec.span("probe"):
        time.sleep(0.05) if work == "sleeps" else _spin(0.05)
    got = rec.spans()[0]
    held = _wall(got) - got["cpu_s"]
    assert 0 <= got["cpu_s"] <= _wall(got)          # the CPU clock is read inside the wall clock's
    if work == "sleeps":                            # by an order of magnitude, not by a margin
        assert got["cpu_s"] < _wall(got) / 10 and held > 0.9 * _wall(got)
    else:
        assert got["cpu_s"] >= 0.05 and got["cpu_s"] > _wall(got) / 10


def test_a_childs_cpu_is_inside_its_parents_and_a_compile_span_has_none():
    rec = spans.Recorder()
    with rec.span("train.step", engine=1) as step:
        with rec.span("train.grad_program", program="p") as call:
            _spin(0.02)
            rec.on_compile_event(BACKEND, 0.01)
        _spin(0.01)
    assert 0.02 <= call.cpu_s <= step.cpu_s - 0.01
    by_name = {s["name"]: s for s in rec.spans()}
    assert by_name["compile.backend"]["cpu_s"] is None
    assert by_name["train.step"]["cpu_s"] == step.cpu_s and "cpu_s" in by_name["train.grad_program"]


class _Loss:
    """A step's loss as the engine keeps it: asked whether it is ready, and nothing else."""

    def __init__(self, ready):
        self.ready, self.asked = ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready

    def __getattr__(self, name):          # block_until_ready, __array__, __float__, ...
        raise AssertionError(f"the engine touched a kept loss: {name}")


def _simple_engine(**config):
    model = SimpleModel(16)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=model.init(jax.random.PRNGKey(0)),
        config_params=simple_config(bf16={"enabled": True}, **config))
    data = random_dataset(8, 16)
    xs = np.stack([d[0] for d in data]).astype(jnp.bfloat16)
    ys = np.stack([d[1] for d in data]).astype(jnp.bfloat16)
    return engine, xs, ys


def _steps_of(engine):
    return [s for s in spans.recorder().spans(engine=engine._span_engine)
            if s["name"] == "train.step"]


def _step(engine, xs, ys):
    loss = engine(xs, ys)
    engine.backward(loss)
    engine.step()
    return loss


def test_in_flight_counts_the_losses_that_are_not_ready_and_waits_for_none():
    engine, xs, ys = _simple_engine()
    _step(engine, xs, ys)
    kept = [_Loss(True), _Loss(False), _Loss(False), _Loss(True), _Loss(False)]
    engine._step_losses.extend(kept)
    _step(engine, xs, ys)
    first, second = _steps_of(engine)
    assert first["attrs"]["in_flight"] == 0          # no step before it
    # the five stand-ins, and the first step's own loss, which may or may not be made yet
    assert second["attrs"]["in_flight"] in (3, 4)
    assert all(k.asked == 1 for k in kept)


def test_the_engine_keeps_its_last_eight_losses_and_no_more():
    engine, xs, ys = _simple_engine()
    losses = [_step(engine, xs, ys) for _ in range(10)]
    assert len(engine._step_losses) == 8
    assert all(a is b for a, b in zip(engine._step_losses, losses[2:]))
    jax.block_until_ready(losses)
    _step(engine, xs, ys)
    flights = [s["attrs"]["in_flight"] for s in _steps_of(engine)]
    assert len(flights) == 11 and flights[0] == 0 and flights[-1] == 0     # a drained device
    assert all(0 <= f <= min(i, 8) for i, f in enumerate(flights))


def test_in_flight_is_kept_on_the_fused_step_path():
    engine, xs, ys = _simple_engine(fused_step=True)
    assert engine._run_fused_step is not None
    _step(engine, xs, ys)
    engine._step_losses.extend([_Loss(False), _Loss(False)])
    _step(engine, xs, ys)
    first, second = _steps_of(engine)
    assert first["attrs"]["in_flight"] == 0 and second["attrs"]["in_flight"] in (2, 3)
    assert len(engine._step_losses) == 4


def test_bytes_in_use_is_absent_and_harmless_where_the_backend_reports_nothing():
    engine, xs, ys = _simple_engine()
    assert all(d.memory_stats() is None for d in engine.mesh.local_devices)    # the CPU
    _step(engine, xs, ys)
    (step,) = _steps_of(engine)
    assert "bytes_in_use" not in step["attrs"] and "bytes_limit" not in step["attrs"]
    assert set(step["attrs"]) == {"in_flight"}


def test_bytes_in_use_is_the_most_over_the_devices_and_the_limit_is_noted_once(monkeypatch):
    from deepspeed_tpu.runtime import engine as engine_module
    engine, xs, ys = _simple_engine()
    devices = list(engine.mesh.local_devices)
    reads = []

    def stats(device):
        reads.append(device)
        n = devices.index(device)
        return {"bytes_in_use": 1000 * len(reads) + n, "peak_bytes_in_use": 10 ** 6,
                "bytes_limit": 5000 + n}

    monkeypatch.setattr(engine_module, "device_memory_stats", stats)
    for _ in range(3):
        _step(engine, xs, ys)
    first, second, third = _steps_of(engine)
    n = len(devices)
    assert reads == devices * 3                         # one read a device a step
    assert first["attrs"]["bytes_in_use"] == 1000 * n + n - 1
    assert third["attrs"]["bytes_in_use"] == 1000 * 3 * n + n - 1
    assert first["attrs"]["bytes_limit"] == 5000        # the least over the devices
    assert "bytes_limit" not in second["attrs"] and "bytes_limit" not in third["attrs"]
    # a device that reports nothing beside one that reports: the one that reports counts
    monkeypatch.setattr(engine_module, "device_memory_stats",
                        lambda d: {"bytes_in_use": 7} if d is devices[0] else None)
    _step(engine, xs, ys)
    assert _steps_of(engine)[-1]["attrs"]["bytes_in_use"] == 7


@pytest.fixture(scope="module")
def simple_catalog():
    """The catalog of the two-layer toy's step programs, compiled once for the cases below."""
    engine, xs, ys = _simple_engine()
    _step(engine, xs, ys)
    return spans.recorder().programs(engine._span_engine)


def test_the_catalog_gives_each_step_programs_memory_as_the_compiler_states_it(simple_catalog):
    catalog = simple_catalog
    assert set(catalog) == {"loss_and_grad", "apply_update"}
    for program in catalog.values():
        assert set(program["memory"]) == {"argument", "output", "alias", "temp", "code"}
        assert all(isinstance(v, int) and v >= 0 for v in program["memory"].values())
        assert program["memory"]["argument"] > 0 and program["memory"]["output"] > 0
    # the update program writes its state over what it was given
    assert catalog["apply_update"]["memory"]["alias"] > 0
    assert spans._memory_sizes(type("C", (), {"memory_analysis": lambda self: None})()) is None


def test_the_catalog_prices_each_operation_of_a_step_program(simple_catalog):
    """``cost``: ``[flops, bytes]`` an instruction the device runs on its own, from the same
    text as ``ops``. The toy is two 16-wide layers on ONE sample a device, so every product
    the compiler keeps as one is a vector times a 16 x 16 matrix: 2 x 16 x 16 operations,
    whatever is fused around it (the two forward products at least; a weight's gradient is
    an outer product, which this compiler writes as a multiplication)."""
    grad, update = simple_catalog["loss_and_grad"], simple_catalog["apply_update"]
    for program in (grad, update):
        assert set(program["cost"]) <= set(program["ops"])
        assert all(len(c) == 2 and c[0] >= 0 and c[1] >= 0 for c in program["cost"].values())
    products = {name: flops for name, (flops, _) in grad["cost"].items() if flops}
    assert 2 <= len(products) <= 5 and set(products.values()) == {2 * 16 * 16}
    assert set(grad["products"]) == set(products)
    for name, product in grad["products"].items():
        (m, k, n, types), = product["mkn"]
        assert (m, k, n) == (1, 16, 16) and product["as"] is None, name
        # what the product reads and writes at the least: a matrix, two vectors
        assert grad["cost"][name][1] >= (16 * 16 + 16 + 16) * 2
    # Adam moves four small leaves: no product, and what it reads and writes has a size
    assert update["products"] == {} and all(flops == 0 for flops, _ in update["cost"].values())
    assert sum(nbytes for _, nbytes in update["cost"].values()) >= 2 * 3 * (2 * 16 * 16 + 2 * 16) * 4 / 8
