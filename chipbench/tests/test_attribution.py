"""The attribution of a traced window to layer scopes and program spans, on a
small trace and module text worked out by hand (data/scoped_trace.pbtxt,
data/scoped.hlo.txt; times in microseconds from the window's start)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import attribution as A
import xplane
from repro.obs import scopes

DATA = Path(__file__).resolve().parent / "data"
US = 1e3  # ns


@pytest.fixture(scope="module")
def events():
    text = ProfileData.from_text_proto((DATA / "scoped_trace.pbtxt").read_text())
    return list(xplane.events(text))


@pytest.fixture(scope="module")
def summary(events):
    return xplane.summarize(events)


def table(name):
    return A.instructions((DATA / name).read_text())


def test_the_program_opens_the_scopes_the_benchmark_reads():
    assert A.SCOPES == scopes.SCOPES


def test_scope_of():
    assert A.scope_of("jit(step)/transpose(jvp(mixer))/ssd/dot_general") == "ssd"
    assert A.scope_of("jit(step)/jvp(mixer)/while/body/mul") == "mixer"
    assert A.scope_of("jit(f)/checkpoint/rematted_computation/mlp/tanh") == "mlp"
    assert A.scope_of("jit(step)/while/body/dynamic_slice") is None
    assert A.scope_of("jit(step)/mlp_proj/dot_general") is None  # whole entries only
    assert A.scope_of("") is None


def test_instruction_table():
    t = table("scoped.hlo.txt")
    # every computation's instructions, fused ones too, by their trace name
    assert t["fusion.1"] == ("fusion.1 f32[8,8]", "ssd")
    assert t["dot.1"] == ("dot.1 f32[8,8]", "ssd")
    assert t["while.5"] == ("while.5", None)
    assert t["copy.4"] == ("copy.4 f32[8]", None)  # no metadata
    assert t["param_0"][1] is None


def test_transforms_stripped_and_innermost_scope_wins():
    t = table("scoped.hlo.txt")
    assert t["fusion.1"][1] == "ssd"  # transpose(jvp(mixer))/ssd
    assert t["fusion.2"][1] == "mixer"  # jvp(mixer)
    assert t["convert.3"][1] == "mlp"  # remat's names match nothing
    assert t["gather.6"][1] == "embed"


def test_time_per_scope(summary):
    sc = A.attribute(summary, [table("decoy.hlo.txt"), table("scoped.hlo.txt")])
    # chip 0: while [10, 60] holds fusion.1 18, fusion.2 10, convert.3 15, so
    # its own time is 7; copy.4 10; fusion.9 10 (in the decoy module only);
    # gather.6 5. Chip 1: fusion.1 40. Mean over the two chips.
    assert sc.ns == pytest.approx({"ssd": 29 * US, "mixer": 5 * US, "mlp": 7.5 * US,
                                   "embed": 2.5 * US, A.UNSCOPED: 13.5 * US})
    assert sc.busy_ns == pytest.approx(57.5 * US)
    # scopes and the unscoped remainder add up to the busy time
    assert sum(sc.ns.values()) == pytest.approx(sc.busy_ns)
    assert sc.seconds("ssd", "mixer") == pytest.approx(34e-6)
    assert sc.share(A.UNSCOPED) == pytest.approx(13.5 / 57.5)
    assert sc.unscoped_ops == [("copy.4 f32[8]", 5 * US), ("fusion.9 f32[8]", 5 * US),
                               ("while.5", 3.5 * US)]


def test_op_types_must_match(summary):
    # the decoy alone: its fusion.1 has another type, so only fusion.9 is found
    sc = A.attribute(summary, [table("decoy.hlo.txt")])
    assert sc.ns == pytest.approx({"loss": 5 * US, A.UNSCOPED: 52.5 * US})


def test_a_program_without_scopes_gives_none(summary):
    bare = {k: (s, None) for k, (s, _) in table("scoped.hlo.txt").items()}
    assert A.attribute(summary, [bare]) is None
    assert A.attribute(summary, []) is None
    # the same program compiled twice, once without its scopes: the scoped one
    sc = A.attribute(summary, [bare, table("scoped.hlo.txt")])
    assert sc.ns["ssd"] == pytest.approx(29 * US)


def test_program_spans_on_the_trace_clock(events):
    # repro.data.batch [-5, 20] clipped to the window and [60, 70];
    # repro.train.step [20, 25]; chipbench.next_batch is not the program's
    assert A.span_totals(events) == pytest.approx(
        {"repro.data.batch": 30e-6, "repro.train.step": 5e-6})


def test_compiled_text_of_a_scoped_step():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((8, 8))
    own = A.instructions(jax.jit(jax.grad(f)).lower(x).compile().as_text())
    ops = {short: 1e3 for short, _ in own.values()}
    chip = xplane.Chip(busy_ns=1e3 * len(ops), collective_exposed_ns=0.0, ops_ns=ops)
    sc = A.attribute(xplane.Summary(2e3 * len(ops), {0: chip}, []), [own])
    n_mlp = sum(scope == "mlp" for _, scope in own.values())
    assert 0 < n_mlp < len(ops)
    assert sc.ns["mlp"] == pytest.approx(1e3 * n_mlp)
    assert sum(sc.ns.values()) == pytest.approx(sc.busy_ns)
