"""``scopes.py``: sections read from a step's compiled HLO and a trace's
device ops, on a handwritten HLO and a small trace in the profiler's own
format; and the program's scopes in both colocated steps, compiled at a
tiny size on the CPU."""
import jax
import pytest

from benchmarks.chip import harness, scopes, trace
from benchmarks.chip.tests.fixtures import SEED, tiny
from benchmarks.chip.tests.test_bench_trace import _xspace

LM = "jit(step)/while/body/closed_call/transpose(jvp(lm))"
HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation.7 (param_0.1: f32[4]) -> f32[4] {{
  %param_0.1 = f32[4]{{0}} parameter(0)
  ROOT %add.9 = f32[4]{{0}} add(%param_0.1, %param_0.1), metadata={{op_name="jit(step)/while/body/closed_call/grad_accum/add" stack_frame_id=3}}
}}

%fused_computation.8 (param_0.2: f32[4], param_1.2: f32[4]) -> (f32[4], f32[4]) {{
  %param_0.2 = f32[4]{{0}} parameter(0)
  %param_1.2 = f32[4]{{0}} parameter(1)
  %sub.10 = f32[4]{{0}} subtract(%param_0.2, %param_1.2), metadata={{op_name="jit(step)/while/body/closed_call/jvp(vit)/while/body/closed_call/sub"}}
  %mul.11 = f32[4]{{0}} multiply(%param_0.2, %param_1.2), metadata={{op_name="jit(step)/while/body/closed_call/jvp(vit)/while/body/closed_call/mul"}}
  ROOT %tuple.12 = (f32[4]{{0}}, f32[4]{{0}}) tuple(%sub.10, %mul.11)
}}

ENTRY %main.1 (p0: f32[4]) -> f32[4] {{
  %p0 = f32[4]{{0}} parameter(0), metadata={{op_name="params"}}
  %fusion.1 = f32[4]{{0:T(256)}} fusion(%p0), kind=kCustom, calls=%fused_computation.7, metadata={{op_name="{LM}/jit(_take)/scatter-add" stack_frame_id=157}}, backend_config={{"flag_configs":[]}}
  %fusion.2 = f32[4]{{0}} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.7, metadata={{op_name="{LM}/while/body/closed_call/checkpoint/flash_bwd/while/body/closed_call/bqkgd,btkd->bkgqt/dot_general"}}
  %flash_attention_fwd.3 = f32[4]{{0}} custom-call(%fusion.2), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="jit(step)/while/body/closed_call/jvp(vit)/while/body/closed_call/checkpoint/rematted_computation/flash_attention_fwd/pallas_call"}}
  %add.4 = f32[4]{{0}} add(%flash_attention_fwd.3, %p0), metadata={{op_name="jit(step)/while/body/closed_call/grad_accum/add"}}
  %copy-start.5 = (f32[4]{{0}}, f32[4]{{0}}, u32[]) copy-start(%p0)
  %subtract_multiply_fusion.7 = (f32[4]{{0}}, f32[4]{{0}}) fusion(%p0, %fusion.2), kind=kLoop, calls=%fused_computation.8, backend_config={{"flag_configs":[]}}
  %copy-start.8 = (f32[4]{{0}}, f32[4]{{0}}, u32[]) copy-start(%fusion.2)
  %copy-done.9 = f32[4]{{0}} copy-done(%copy-start.8)
  %constant.13 = f32[] constant(0)
  ROOT %fusion.6 = f32[4]{{0}} fusion(%add.4), kind=kLoop, calls=%fused_computation.7, metadata={{op_name="jit(step)/optimizer/jit(_where)/select_n"}}
}}
"""


def test_components_strip_transform_wrappers():
    assert scopes.components(
        "jit(step)/while/body/closed_call/transpose(jvp(vit))/while/body/"
        "closed_call/checkpoint/flash_bwd/mul") == ("step", "vit",
                                                    "flash_bwd", "mul")
    assert scopes.components(
        "checkpoint/rematted_computation/remat(core)/reduce_sum") == (
        "core", "reduce_sum")
    # a gather outside every scope, under a transform with nothing named
    assert scopes.components("jvp()/gather") == ("gather",)
    # a whole component is matched, never a part of one
    assert "lm" not in scopes.components("jit(lm_forward)/dot_general")


def test_op_scopes_of_compiled_text():
    names = scopes.op_scopes(HLO)
    assert names["fusion.1"] == ("step", "lm", "_take", "scatter-add")
    assert names["fusion.2"][:3] == ("step", "lm", "flash_bwd")
    assert names["flash_attention_fwd.3"] == (
        "step", "vit", "flash_attention_fwd", "pallas_call")
    assert names["add.4"] == names["add.9"] == ("step", "grad_accum", "add")
    assert names["fusion.6"] == ("step", "optimizer", "_where", "select_n")
    assert names["p0"] == ("params",)
    # no metadata of its own: a fusion takes the path its fused
    # instructions share, an async copy its operand's
    assert names["subtract_multiply_fusion.7"] == ("step", "vit")
    assert names["copy-done.9"] == names["copy-start.8"] == names["fusion.2"]
    assert names["copy-start.5"] == ("params",)
    assert "constant.13" not in names and "param_0.2" not in names


class _Compiled:
    def __init__(self, text):
        self.text = text

    def compile(self):
        return self

    def as_text(self):
        return self.text


class _Step:
    """A jitted step's stand-in: lowering records its arguments."""

    def __init__(self, text):
        self.text, self.lowered_with = text, None

    def __call__(self, *args):
        raise AssertionError("the step ran")

    def lower(self, *args):
        self.lowered_with = args
        return _Compiled(self.text)


class _Job:
    """The interface of a path's ``Job`` that ``scopes.lowered`` uses."""

    def __init__(self, text):
        self.step, self.state = _Step(text), "live"

    def prepare(self, i):
        return {"batch": i}

    def dispatch(self, batch, i):
        self.state, m = self.step(self.state, batch, i)
        return m


def _run(steps=2, chips=1):
    """A traced run of ``steps`` steps: one device, window 0-1000 ns; a
    loop (``while.0``) holds the two LM ops, which overlap; the copy
    overlaps the accumulation's add and holds no section scope."""
    t = trace.from_profile(_xspace({
        "/device:TPU:0": {"XLA Ops": [
            ("%while.0", 50, 450),
            ("%fusion.1 = f32[4]{0} fusion(%p0)", 100, 300),
            ("%fusion.2", 250, 400),
            ("%flash_attention_fwd.3", 500, 600),
            ("%add.4", 650, 700), ("%copy-start.5", 680, 720),
            ("%fusion.6", 800, 900), ("%fusion.1", 990, 1100)]},
        "/host:CPU": {"python": [("window", 0, 1000)]}}))
    logged = []
    return harness.Run(job=_Job(HLO), trace=t, steps=[None] * steps,
                       chips=chips, log=logged.append), logged


def test_lowered_catches_the_dispatched_call():
    job = _Job(HLO)
    assert scopes.lowered(job).as_text() == HLO
    assert job.step.lowered_with == ("live", {"batch": 0}, 0)
    assert job.state == "live" and isinstance(job.step, _Step)


def test_scope_ms_is_the_union_per_step():
    run, logged = _run()
    # the two LM ops overlap (100-300, 250-400): their union counts once,
    # and the op that runs past the window is clipped to it (990-1000)
    assert scopes.scope_ms(run, "lm") == pytest.approx((300 + 10) / 2 / 1e6)
    assert scopes.scope_ms(run, "flash_bwd") == pytest.approx(150 / 2 / 1e6)
    assert scopes.scope_ms(run, "vit") == pytest.approx(100 / 2 / 1e6)
    assert scopes.scope_ms(run, "grad_accum") == pytest.approx(50 / 2 / 1e6)
    assert scopes.scope_ms(run, "optimizer") == pytest.approx(100 / 2 / 1e6)
    # a scope no op holds reads nothing, as on a program without scopes
    assert scopes.scope_ms(run, "teacher") is None
    # compiled once a run, and logged once
    assert len(logged) == 1 and "unscoped share" in logged[0]


def test_scope_ms_divides_by_steps_and_chips():
    one = scopes.scope_ms(_run(steps=1)[0], "lm")
    assert scopes.scope_ms(_run(steps=4)[0], "lm") == pytest.approx(one / 4)
    assert scopes.scope_ms(_run(steps=1, chips=2)[0], "lm") == \
        pytest.approx(one / 2)


def test_unscoped_share_and_top_unscoped():
    run, _ = _run()
    # busy: 50-450 (the loop), 500-600, 650-720, 800-900, 990-1000 = 680;
    # sections: 100-400, 500-600, 650-700, 800-900, 990-1000 = 560
    assert scopes.unscoped_share(run) == pytest.approx(1 - 560 / 680)
    # the loop holds ops, so it is no leaf; the copy is the one leaf
    assert scopes.top_unscoped(run) == [["copy-start.5",
                                         pytest.approx(40 / 2 / 1e6)]]


#: the scopes each colocated step must carry
EXPECTED = {"mllm": {"vit", "lm", "grad_accum", "optimizer", "flash_bwd"},
            "distill": {"teacher", "student", "grad_accum", "optimizer",
                        "flash_bwd"}}


@pytest.mark.parametrize("which", sorted(EXPECTED))
def test_colocated_steps_carry_their_section_scopes(which):
    c, t = tiny(which)
    job = harness.path_module(t["path"]).Job(c, t, SEED, jax.devices()[:1],
                                             harness.Spans())
    names = scopes.op_scopes(scopes.lowered(job).compile().as_text())
    job.free()
    found = {s for comps in names.values() for s in comps}
    assert EXPECTED[which] <= found
    # and none of the other workload's sections
    assert not {"vit", "lm", "teacher", "student"} - EXPECTED[which] & found
