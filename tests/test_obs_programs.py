"""The compiled-program catalog (obs/programs.py) and the scopes it reads
(nn/core.py, nn/layers.py, nn/resnet.py, train/zoo.py:make_train_step).

- parse on a step compiled here (tiny ResNet-18, CPU), on hand-written
  HLO text, and on a pair recorded on a v5e: `jit_step`'s HLO text and a
  one-step device trace of the same program;
- the conv scopes of the benchmark's two configurations against the
  names `benchmark/flops.py` gives the same convs (jaxpr only, no compile);
- scopes are metadata: patched out, two steps give the same bits;
- `zoo.train` records a catalog when it is handed a tracer, and only then.
"""

import contextlib
import gzip
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.nn import resnet
from parallel_cnn_tpu.nn.core import Sequential
from parallel_cnn_tpu.nn.layers import ConvBNAct, Dense, GlobalAvgPool
from parallel_cnn_tpu.obs import programs
from parallel_cnn_tpu.train import zoo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import flops, trace_reduce  # noqa: E402

pytestmark = pytest.mark.obs

DATA = os.path.join(ROOT, "tests", "benchmark", "data")


def _like(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


# ---------------------------------------------------------- op_name -> scope

@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/grad/jvp(s1b1)/mid/conv/conv_general_dilated",
     ("s1b1/mid/conv", "fwd")),
    ("jit(step)/grad/transpose(jvp(s1b1))/mid/conv/conv_general_dilated",
     ("s1b1/mid/conv", "bwd")),
    ("jit(step)/grad/jvp(stem)/bn/jit(_var)/sub", ("stem/bn", "fwd")),
    ("jit(step)/grad/jvp(s2b1)/tail/act/jit(relu)/max", ("s2b1/tail/act", "fwd")),
    # relu is a custom_jvp: its backward wraps `grad` itself
    ("jit(step)/grad/transpose(grad)/jvp(s2b1)/tail/act/select_n",
     ("s2b1/tail/act", "bwd")),
    ("jit(step)/grad/jvp(jit(take_along_axis))", ("grad", "fwd")),
    ("jit(step)/grad/transpose(jvp(fc))/dot_general", ("fc", "bwd")),
    ("jit(step)/grad/reduce_sum", ("grad", "fwd")),
    ("jit(step)/optimizer/add", ("optimizer", "opt")),
    ("jit(step)/optimizer/jit(_where)/select_n", ("optimizer", "opt")),
    ("jit(step)/sharding_constraint", ("", "")),
    ("state.params[0]['conv']['w']", ("", "")),
    ("reduce_sum", ("", "")),
    ("", ("", "")),
])
def test_scope_and_phase_of_an_op_name(op_name, want):
    assert programs.scope_of(op_name) == want


# ------------------------------------------------------ hand-written HLO text

HLO = """HloModule jit_step, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation.1 (p0: bf16[8,4,4,8], p1: bf16[3,3,8,8]) -> bf16[8,4,4,8] {
  %p0 = bf16[8,4,4,8]{3,2,1,0} parameter(0)
  %p1 = bf16[3,3,8,8]{3,2,1,0} parameter(1)
  %convolution.7 = bf16[8,4,4,8]{3,2,1,0} convolution(%p0, %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/grad/transpose(jvp(s1b1))/head/conv/conv_general_dilated"}
  %mul.1 = bf16[8,4,4,8]{3,2,1,0} multiply(%convolution.7, %convolution.7), metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %add.1 = bf16[8,4,4,8]{3,2,1,0} add(%mul.1, %mul.1), metadata={op_name="jit(step)/optimizer/add"}
}

%fused_computation.2 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  %x.1 = f32[8]{0} multiply(%p0.1, %p0.1), metadata={op_name="jit(step)/grad/jvp(s1b1)/head/bn/mul"}
  %x.2 = f32[8]{0} add(%x.1, %p0.1), metadata={op_name="jit(step)/grad/jvp(s1b1)/head/bn/add"}
  ROOT %x.3 = f32[8]{0} negate(%x.2), metadata={op_name="jit(step)/grad/jvp(s1b1)/tail/bn/neg"}
}

%fused_computation.3 (p0.2: f32[8]) -> f32[8] {
  %p0.2 = f32[8]{0} parameter(0)
  ROOT %c.1 = f32[8]{0} copy(%p0.2)
}

%body.1 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %g.1 = f32[8]{0} get-tuple-element(%t), index=1
  %neg.9 = f32[8]{0} negate(%g.1), metadata={op_name="jit(step)/optimizer/while/body/neg"}
  %g.0 = s32[] get-tuple-element(%t), index=0
  ROOT %t.1 = (s32[], f32[8]{0}) tuple(%g.0, %neg.9)
}

%cond.1 (t.2: (s32[], f32[8])) -> pred[] {
  %t.2 = (s32[], f32[8]{0}) parameter(0)
  %g.2 = s32[] get-tuple-element(%t.2), index=0
  %k = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(%g.2, %k), direction=LT
}

ENTRY %main.9 (x: bf16[8,4,4,8], w: bf16[3,3,8,8], v: f32[8]) -> (bf16[8,4,4,8], f32[8]) {
  %x = bf16[8,4,4,8]{3,2,1,0} parameter(0), metadata={op_name="x"}
  %w = bf16[3,3,8,8]{3,2,1,0} parameter(1), metadata={op_name="state.params[0]"}
  %v = f32[8]{0} parameter(2)
  %fusion.12 = bf16[8,4,4,8]{3,2,1,0} fusion(%x, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/add"}
  %multiply_add_fusion = f32[8]{0} fusion(%v), kind=kLoop, calls=%fused_computation.2
  %copy_fusion.3 = f32[8]{0} fusion(%v), kind=kLoop, calls=%fused_computation.3
  %reduce.4 = f32[] reduce(%v, %v), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/grad/jvp(fc)/reduce_sum"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %multiply_add_fusion)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond.1, body=%body.1
  %gte = f32[8]{0} get-tuple-element(%while.1), index=1
  ROOT %out = (bf16[8,4,4,8]{3,2,1,0}, f32[8]{0}) tuple(%fusion.12, %gte)
}
"""


def test_parse_names_a_fusion_by_its_hero_and_follows_what_executes():
    cat = programs.parse(HLO)
    # the member convolution wins over the fusion's own (optimizer) name
    assert cat["fusion.12"] == programs.Entry("s1b1/head/conv", "bwd", "fusion", True)
    # no op_name of its own: what most named members share
    assert cat["multiply_add_fusion"] == programs.Entry("s1b1/head/bn", "fwd", "fusion", False)
    # nothing to name it by
    assert cat["copy_fusion.3"] == programs.Entry("", "", "fusion", False)
    assert cat["reduce.4"] == programs.Entry("fc", "fwd", "reduce", False)
    # a while's body and condition execute on their own; a reduce's
    # to_apply and a fusion's members do not
    assert cat["neg.9"].phase == "opt" and "lt.1" in cat
    assert not {"add.0", "convolution.7", "x.1", "c.1"} & set(cat)
    assert all((e.phase == "") == (e.scope == "") for e in cat.values())
    assert programs.parse("") == {} and programs.parse("HloModule empty\n") == {}


def test_store_is_last_record_wins_and_export_writes_what_lookup_gives(tmp_path):
    assert programs.lookup("jit_step") is None
    assert programs.export(str(tmp_path / "none.json")) is None
    assert not (tmp_path / "none.json").exists()
    programs.record("jit_step", "HloModule other\n")
    programs.record("jit_step", HLO)
    assert programs.lookup("jit_step")["fusion.12"].has_conv
    with open(programs.export(str(tmp_path / "p.json"))) as f:
        got = json.load(f)
    assert got["jit_step"]["fusion.12"] == {
        "scope": "s1b1/head/conv", "phase": "bwd", "opcode": "fusion",
        "has_conv": True}
    programs.clear()
    assert programs.lookup("jit_step") is None


def test_obs_finish_writes_the_catalog_beside_the_trace(tmp_path):
    from parallel_cnn_tpu.config import ObsConfig

    def finish(run):
        bundle = obs_lib.from_config(
            ObsConfig(trace=True, dir=str(tmp_path), jax_annotations=False),
            run=run)
        with bundle.span("s", step=3):
            pass
        return bundle.finish()

    assert "programs" not in finish("before")  # nothing recorded, no file
    programs.record("jit_step", HLO)
    arts = finish("after")
    assert arts["programs"] == str(tmp_path / "after_programs.json")
    with open(arts["programs"]) as f:
        assert "multiply_add_fusion" in json.load(f)["jit_step"]


# ------------------------------------------- a step compiled here, on the CPU

@pytest.fixture(scope="module")
def tiny_r18():
    model = resnet.resnet18(10, cifar_stem=False)
    opt = zoo.make_optimizer(0.1, 0.9, 1e-4)
    state = jax.eval_shape(
        lambda k: zoo.init_state(model, k, (32, 32, 3), opt), jax.random.key(0))
    x = jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.bfloat16)
    y = jax.ShapeDtypeStruct((8,), jnp.int32)
    text = zoo.make_train_step(model, opt).lower(state, x, y).compile().as_text()
    return text, programs.parse(text)


def test_every_entry_level_instruction_of_a_compiled_step_has_an_entry(tiny_r18):
    text, cat = tiny_r18
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    names = [trace_reduce.parse_op(l.strip().removeprefix("ROOT "))[0]
             for l in entry.splitlines()[2:] if " = " in l]
    assert len(names) > 300 and set(names) <= set(cat)
    assert all((e.phase == "") == (e.scope == "") for e in cat.values())


def test_a_compiled_step_has_all_three_phases_and_layer_scopes(tiny_r18):
    _, cat = tiny_r18
    by_phase = {p: {e.scope for e in cat.values() if e.phase == p}
                for p in ("fwd", "bwd", "opt")}
    assert by_phase["opt"] == {"optimizer"}
    for phase in ("fwd", "bwd"):
        assert {"stem/bn", "s1b1/head/conv", "s2b1/proj/conv",
                "s4b2/tail/bn", "fc"} <= by_phase[phase], phase
        assert not any("(" in s or s.startswith("grad/") for s in by_phase[phase])
    assert {"stem/conv", "pool", "grad"} <= by_phase["fwd"]
    convs = {e.scope for e in cat.values() if e.has_conv and e.scope}
    assert convs and all(s.endswith("/conv") for s in convs)


# ----------------------------------------------- the pair recorded on a v5e

@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "r18_jit_step.hlo.txt.gz"), "rt") as f:
        cat = programs.parse(f.read())
    with gzip.open(os.path.join(DATA, "r18_jit_step_one_step.xplane.pb.gz")) as f:
        trace = trace_reduce.read_xplane(f.read())
    return cat, trace


def test_recorded_trace_joins_the_recorded_program_by_instruction_name(recorded):
    cat, trace = recorded
    (run,) = trace.runs(0, r"^jit_step\b")
    inside = [o for o in trace.ops[0] if run[0] <= o.start < run[1]]
    whole = sum(o.end - o.start for o in inside)
    joined = sum(o.end - o.start for o in inside if o.name in cat)
    named = sum(o.end - o.start for o in inside
                if o.name in cat and cat[o.name].scope)
    assert len(inside) > 1000 and joined >= 0.99 * whole
    assert named >= 0.80 * whole


def test_recorded_program_names_its_conv_fusions_by_their_conv(recorded):
    cat, trace = recorded
    conv_fusions = {n: e for n, e in cat.items()
                    if e.opcode == "fusion" and e.has_conv}
    assert len(conv_fusions) >= 55  # 20 forward, 19 dgrad, 20 wgrad (some merged)
    # on the TPU the dense head's matmuls are `convolution`s too
    assert all((e.scope.endswith("/conv") or e.scope == "fc")
               and e.phase in ("fwd", "bwd") for e in conv_fusions.values())
    assert {e.scope for e in conv_fusions.values() if e.phase == "fwd"} == {
        l["name"].replace(".", "/") + "/conv" if l["kind"] == "conv" else l["name"]
        for l in flops.layers(_config("resnet18_imagenet"))}
    # what the trace reduction calls a conv (any kind=kOutput fusion) is
    # what the catalog calls one — but for the max-pool's forward, a
    # kOutput fusion around a reduce-window that holds no convolution
    traced = {o.name for o in trace.ops[0] if o.category == "conv"}
    extra = traced - {n for n, e in cat.items() if e.has_conv}
    assert len(traced) >= 55 and {cat[n].scope for n in extra} == {"pool"}


# ------------------------------ conv scopes against the shape counter's names

def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def _name_stacks(jaxpr, primitive, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            out.append(str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _name_stacks(sub, primitive, out)
    return out


@pytest.mark.parametrize("name", ["resnet18_imagenet", "resnet50_imagenet"])
def test_conv_scopes_are_the_shape_counters_layer_names(name):
    cfg = _config(name)
    fac = cfg["factory"]
    model = getattr(resnet, fac["name"])(**fac["kwargs"])
    in_shape = tuple(cfg["input"])
    params, state, _ = jax.eval_shape(
        lambda k: model.init(k, in_shape), jax.random.key(0))
    x = jax.ShapeDtypeStruct((2, *in_shape), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda p, s, x: model.apply(p, s, x, train=True))(params, state, x)
    stacks = _name_stacks(jaxpr.jaxpr, "conv_general_dilated", [])
    # ConvBNAct scopes `add` only where it is passed a residual
    adds = {s for s in _name_stacks(jaxpr.jaxpr, "add", []) if s.endswith("/add")}
    assert adds and all(s.split("/")[-2] in ("tail", "expand") for s in adds)
    want = [l["name"] for l in flops.layers(cfg) if l["kind"] == "conv"]
    got = [s.removesuffix("/conv").replace("/", ".") for s in stacks]
    assert all(s.endswith("/conv") for s in stacks)
    assert sorted(got) == sorted(want) and len(set(got)) == len(got)


# --------------------------------------------------- scopes are metadata only

def _tiny_model():
    return Sequential([ConvBNAct(8), ConvBNAct(8, relu=False),
                       GlobalAvgPool(), Dense(10)])


def _two_steps(model):
    opt = zoo.make_optimizer(0.1, 0.9, 1e-4)
    state = zoo.init_state(model, jax.random.key(3), (8, 8, 3), opt)
    x = jax.random.normal(jax.random.key(4), (4, 8, 8, 3))
    y = jnp.arange(4) % 10
    step = zoo.make_train_step(model, opt)
    text = step.lower(_like(state), _like(x), _like(y)).compile().as_text()
    losses = []
    for _ in range(2):
        state, loss = step(state, x, y)
        losses.append(np.asarray(loss))
    return losses, state, text


def test_two_steps_are_bit_identical_with_the_scopes_patched_out(monkeypatch):
    losses, state, text = _two_steps(_tiny_model())
    assert "jvp(0.ConvBNAct)/conv/" in text and "/optimizer/" in text
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    losses0, state0, text0 = _two_steps(_tiny_model())
    assert "0.ConvBNAct" not in text0 and "/optimizer/" not in text0
    assert all(a.tobytes() == b.tobytes() for a, b in zip(losses, losses0))
    leaves, leaves0 = (jax.tree_util.tree_leaves(s) for s in (state, state0))
    assert len(leaves) == len(leaves0)
    assert all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
               for a, b in zip(leaves, leaves0))


def test_names_are_not_part_of_what_init_returns():
    plain = _tiny_model()
    named = Sequential(plain.layers, ["a", "b", "gap", "fc"])
    assert plain.scope_names() == ["0.ConvBNAct", "1.ConvBNAct",
                                   "2.GlobalAvgPool", "3.Dense"]
    out = [jax.eval_shape(lambda k: m.init(k, (8, 8, 3)), jax.random.key(0))
           for m in (plain, named)]
    assert jax.tree_util.tree_structure(out[0]) == jax.tree_util.tree_structure(out[1])
    assert [a.shape for a in jax.tree_util.tree_leaves(out[0])] == \
        [a.shape for a in jax.tree_util.tree_leaves(out[1])]
    r18 = resnet.resnet18(10, cifar_stem=False)
    assert r18.scope_names() == ["stem", "pool", "s1b1", "s1b2", "s2b1", "s2b2",
                                 "s3b1", "s3b2", "s4b1", "s4b2", "gap", "fc"]
    assert resnet.resnet18(10).scope_names()[:2] == ["stem", "s1b1"]
    with pytest.raises(ValueError, match="names"):
        Sequential(plain.layers, ["a"]).scope_names()


# ------------------------------------------------- zoo.train and the catalog

def _train(obs):
    x = jax.random.normal(jax.random.key(5), (16, 8, 8, 3))
    y = jnp.arange(16) % 10
    return zoo.train(_tiny_model(), x, y, in_shape=(8, 8, 3), epochs=2,
                     batch_size=8, lr=0.05, seed=1, verbose=False, obs=obs)


def _traced_obs():
    return obs_lib.Obs(obs_lib.Tracer(mirror_jax=False),
                       obs_lib.MetricsRegistry(), obs_lib.NOOP_JOURNAL,
                       enabled=True)


@pytest.mark.parametrize("traced", [False, True], ids=["obs_none", "obs_on"])
def test_zoo_train_records_a_catalog_only_when_handed_a_tracer(traced):
    obs = _traced_obs() if traced else None
    _train(obs)
    cat = programs.lookup("jit_step")
    if not traced:
        assert cat is None
        return
    assert {e.phase for e in cat.values()} == {"fwd", "bwd", "opt", ""}
    assert "0.ConvBNAct/conv" in {e.scope for e in cat.values()}


def test_the_catalog_compile_is_a_load_from_the_compile_cache(tmp_path):
    """Recording the catalog adds no entry to the persistent compile
    cache: the loop's own call already wrote the one it loads.
    (`ShapeDtypeStruct`s in place of the arrays lower to another key —
    a second full compile, 39 s for ResNet-50 on the v5e.)"""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}

    def entries(sub, obs):
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / sub))
        cc.reset_cache()
        _train(obs)
        return sorted(f.split("-")[0] for f in os.listdir(tmp_path / sub)
                      if f.startswith("jit_step"))

    try:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        plain, traced = entries("off", None), entries("on", _traced_obs())
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert plain and traced == plain
    assert programs.lookup("jit_step")


def test_loop_spans_carry_step_and_epoch_ids():
    obs = _traced_obs()
    _train(obs)
    spans = [e for e in obs.tracer.events() if e.get("ph") == "X"]
    dispatch = [e["args"] for e in spans if e["name"] == "zoo.dispatch"]
    assert dispatch == [{"step": s, "epoch": 1 + s // 2} for s in range(4)]
    data = [e["args"] for e in spans if e["name"] == "zoo.data"]
    assert len(data) == 6 and data[0] == {"step": 0, "epoch": 1}
    assert [e["args"] for e in spans if e["name"] == "zoo.readback"] == [
        {"epoch": 1}, {"epoch": 2}]
    assert not [e for e in spans if e["name"] == "zoo.shard"]  # no mesh


def test_zoo_shard_span_wraps_the_batch_layout_on_the_mesh_path():
    from parallel_cnn_tpu import plan as plan_lib

    mesh = plan_lib.ExecutionPlan(data=4).validate().make_mesh(
        devices=jax.devices()[:4])
    obs = _traced_obs()
    x = jax.random.normal(jax.random.key(5), (16, 8, 8, 3))
    zoo.train(_tiny_model(), x, jnp.arange(16) % 10, in_shape=(8, 8, 3),
              epochs=1, batch_size=8, lr=0.05, seed=1, verbose=False,
              mesh=mesh, obs=obs)
    shard = [e["args"] for e in obs.tracer.events() if e.get("name") == "zoo.shard"]
    assert shard == [{"step": 0, "epoch": 1}, {"step": 1, "epoch": 1}]
    # the catalog is the program of the loop's later calls: state replicated
    assert "fwd" in {e.phase for e in programs.lookup("jit_step").values()}
