"""Device time by layer: the innermost-operation reduction on hand-made
planes, the HLO text's metadata on programs compiled here, and the
training cell's rules on its own step."""
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from perfbench.lib import harness, hlo_scopes, xtrace
from perfbench.lib.hlo_scopes import Rule
from perfbench.tests import cellfiles

SCOPES = ("attention", "mlp", "head", "optimizer")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=list(stats.items()))


# a while loop whose body ran two operations under two scopes, and one
# operation under none; the names are those of HLO_TEXT
PLANES = [
    NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 0, 2000)])]),
    NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step(7)", 0, 1300)]),
        NS(name="XLA Ops", events=[
            ev("%while.1 = (f32[8]) while(...)", 0, 1000),
            ev("%fusion.1 = f32[8] fusion(...)", 100, 200),
            ev("%fusion.2 = f32[8] fusion(...)", 400, 300),
            ev("%copy.3 = f32[8] copy(...)", 1100, 200)])])]

HLO_TEXT = """HloModule jit_step, entry_computation_layout={(f32[8])->f32[8]}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %dot.1 = f32[8] dot(f32[8] %p, f32[8] %p), metadata={op_name="jit(step)/jvp()/while/body/attention/dot_general"}
}

%body (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  %fusion.1 = f32[8] fusion(f32[8] %x), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp()/while/body/attention/add"}
  ROOT %fusion.2 = f32[8] fusion(f32[8] %fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/transpose(jvp())/while/body/mlp/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %while.1 = f32[8] while(f32[8] %a), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp()/while"}
  ROOT %copy.3 = f32[8] copy(f32[8] %while.1)
}
"""


def test_each_instant_goes_once_to_the_innermost_operation_and_its_scope():
    s = xtrace.reduce_profile(PLANES)
    assert s.op_s["jit_step:while.1"] == pytest.approx(500e-9)
    assert s.op_s["jit_step:fusion.1"] == pytest.approx(200e-9)
    assert s.op_s["jit_step:fusion.2"] == pytest.approx(300e-9)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)
    by = hlo_scopes.split(s.op_s, {"jit_step": hlo_scopes.parse(HLO_TEXT)},
                          SCOPES, ())
    assert by == {("attention", "fwd"): pytest.approx(200e-9),
                  ("mlp", "bwd"): pytest.approx(300e-9),
                  ("other", "fwd"): pytest.approx(500e-9),
                  ("other", "none"): pytest.approx(200e-9)}
    assert sum(by.values()) == pytest.approx(s.busy_s)


def test_an_operation_the_text_does_not_name_is_other():
    by = hlo_scopes.split({"jit_step:fusion.9": 1.0, "jit_other:x.1": 2.0},
                          {"jit_step": hlo_scopes.parse(HLO_TEXT)}, SCOPES,
                          ())
    assert by == {("other", "none"): 3.0}


def test_self_times_with_nesting_and_partial_overlap():
    got = xtrace.self_times([(0, 10, "outer"), (2, 4, "a"), (3, 6, "b"),
                             (12, 13, "c")])
    assert got == {"outer": 6, "a": 1, "b": 3, "c": 1}


def two_scopes(w, x):
    with jax.named_scope("attention"):
        y = jnp.tanh(x @ w)
    with jax.named_scope("mlp"):
        z = jax.nn.relu(y @ w.T)
    return jnp.sum(z * z)


def layered(params, x):
    """Two scopes in the body of a scanned, rematerialised layer."""
    def body(h, w):
        return jax.checkpoint(lambda h: two_scopes(w, h) * 0 + h)(h), None
    return jnp.sum(jax.lax.scan(body, x, params)[0])


def compiled_text(fn, *args):
    return jax.jit(jax.grad(fn)).lower(*args).compile().as_text()


def products(text):
    return [line.split("=", 1)[0].split()[-1].lstrip("%")
            for line in text.splitlines()
            if " dot(" in line or " convolution(" in line]


@pytest.fixture
def full_tracebacks():
    old = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    yield
    jax.config.update("jax_include_full_tracebacks_in_locations", old)


def test_the_parser_reads_two_named_scopes_from_a_compiled_program(
        full_tracebacks):
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    text = compiled_text(two_scopes, w, x)
    ops = hlo_scopes.parse(text)
    got = {(hlo_scopes.layer_of(ops[n], SCOPES, ()), ops[n].pass_)
           for n in products(text) if n in ops}
    assert {("attention", "fwd"), ("mlp", "fwd"), ("attention", "bwd"),
            ("mlp", "bwd")} <= got
    # the frames name this file and the function that traced each product
    here = Rule("here", "perfbench/tests/test_hlo_scopes.py", ("two_scopes",))
    assert {hlo_scopes.layer_of(ops[n], (), (here,)) for n in products(text)
            if n in ops} == {"here"}


def test_scopes_survive_a_rematerialised_layer_scan(full_tracebacks):
    params, x = jnp.ones((3, 16, 16)), jnp.ones((4, 16))
    text = compiled_text(layered, params, x)
    ops = hlo_scopes.parse(text)
    got = {hlo_scopes.layer_of(ops[n], SCOPES, ()) for n in products(text)
           if n in ops}
    assert got == {"attention", "mlp"}


def test_function_at_names_methods_and_counts_closures_in_their_definer(
        tmp_path):
    src = tmp_path / "m.py"
    src.write_text("def f():\n    def g():\n        return 1\n    return g\n"
                   "\n\nclass C:\n    @staticmethod\n    def h():\n"
                   "        return 2\n")
    assert hlo_scopes.function_at(str(src), 3) == "f"
    assert hlo_scopes.function_at(str(src), 8) == "C.h"
    assert hlo_scopes.function_at(str(src), 10) == "C.h"
    assert hlo_scopes.function_at(str(src), 6) == "<module>"
    assert hlo_scopes.function_at(str(tmp_path / "none.py"), 1) == "<module>"


class StubDriver:
    def __init__(self, by, steps):
        self.by, self.steps = by, steps

    def layer_split(self, trace):
        return self.by, self.steps


READERS = {"train.attention_fwd_ms": 2.0, "train.mlp_fwd_ms": 3.0,
           "train.layers_ms": 2.0 + 5.0 + 3.0 + 7.0 + 11.0,
           "train.head_ms": 13.0 + 17.0, "train.optimizer_ms": 19.0}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_layer_reader_gives_device_ms_a_step(name):
    by = {("attention", "fwd"): 2.0, ("attention", "bwd"): 5.0,
          ("mlp", "fwd"): 3.0, ("layers", "fwd"): 7.0, ("layers", "bwd"): 11.0,
          ("head", "fwd"): 13.0, ("head", "bwd"): 17.0,
          ("optimizer", "none"): 19.0, ("other", "none"): 23.0}
    read = harness.load_reader(name)
    ctx = {"driver": StubDriver({k: v / 1e3 for k, v in by.items()}, 1),
           "trace": object()}
    assert read(ctx) == pytest.approx(READERS[name])
    assert read({"driver": StubDriver({("other", "none"): 1.0}, 4),
                 "trace": object()}) is None
    assert read({"driver": StubDriver(by, 1), "trace": None}) is None


BENCH = harness.load_benchmark()
TRAINING = [w["name"] for w in BENCH["workloads"]
            if harness.find_cell(BENCH, w["name"])[3]["kind"] == "lm_train"]


@pytest.mark.parametrize("workload", TRAINING)
def test_every_product_of_the_training_step_has_a_layer(workload,
                                                        full_tracebacks):
    """A training cell at its CPU size: every matrix product of its
    compiled step falls in a layer of its architecture's rules, the head
    and at least one other layer among the forward pass's, and the
    optimizer's operations are found."""
    _, _, config, traffic = harness.find_cell(BENCH, workload)
    sizes = cellfiles.overrides(workload)
    config = {**config, **sizes["config"]}
    traffic = {**traffic, **sizes["traffic"]}
    driver = harness.load_kind(traffic["kind"]).Cell(config, traffic, 3)
    try:
        driver.setup()
        driver.window(1.0, traced=False)
        driver.release()
        module, text = driver.step_hlo()
        counters = driver.counters()
    finally:
        driver.close()
    assert module == "jit_train_step"
    assert "loss" in counters
    ops = hlo_scopes.parse(text)
    arch = driver.arch
    layers = {n: (hlo_scopes.layer_of(ops[n], arch.SCOPES, arch.LAYERS),
                  ops[n].pass_) for n in products(text) if n in ops}
    assert layers and all(layer != "other" for layer, _ in layers.values())
    forward = {layer for layer, p in layers.values() if p == "fwd"}
    assert "head" in forward and len(forward) > 1, forward
    trace = NS(op_s={f"{module}:{n}": 1.0 for n in ops},
               busy_s=float(len(ops)),
               module_seconds=lambda m: (float(len(ops)), 2))
    by, steps = driver.layer_split(trace)
    assert steps == 2 and by[("optimizer", "none")] > 0
    assert sum(by.values()) == pytest.approx(len(ops))
