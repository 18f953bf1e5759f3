"""Named scopes inside the train and sync programs, and the engine's host
spans on the profiler's clock.

The scopes change only HLO metadata: the compiled programs carry the names,
every sync instruction that does work sits under exactly one of the four
sync scopes, and with the metadata stripped each program compiles to the
same HLO as a build without scopes. ``run_hfl`` under ``jax.profiler``
records the ``hfl.*`` spans, nested as the engine opens them, and leaves the
state bit-identical."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HFLConfig, ModelConfig, parse_tiers_spec
from repro.core import hfl as H
from repro.core import schedule
from repro.launch.steps import make_loss_fn
from repro.models.transformer import init_model
from repro.optim import SGDM, constant_lr

SYNC_SCOPES = ("sync.select", "sync.compact", "sync.exchange", "sync.merge")
TRAIN_SCOPES = ("train.grad", "train.optimizer")
SYNCS = [("sparse", "topk"), ("sparse", "fused"), ("sparse", "hist"),
         ("dense", "topk")]
CFG = ModelConfig(name="t", arch_type="dense", num_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=61,
                  dtype="float32")
OPT = SGDM(momentum=0.9)
SEQ = 16
TIERS = parse_tiers_spec("2x2:H=2")  # two clusters of two MUs


def _hfl(**kw):
    return HFLConfig(tiers=TIERS, **kw)


def _state(hfl):
    return H.hfl_init(init_model(jax.random.PRNGKey(0), CFG), OPT, hfl)


def _batch(hfl, i=0):
    toks = (jnp.arange(hfl.num_clusters * 2 * SEQ, dtype=jnp.int32) * 7
            + i) % CFG.vocab_size
    return {"tokens": toks.reshape(hfl.num_clusters, 2, SEQ)}


def _train_lowered():
    hfl = _hfl()
    step = jax.jit(H.make_cluster_train_step(make_loss_fn(CFG), OPT,
                                             constant_lr(0.1)),
                   donate_argnums=0)
    return step.lower(_state(hfl), _batch(hfl))


def _sync_lowered(mode, impl):
    hfl = _hfl(sync_mode=mode, omega_impl=impl)
    sync = H.jit_sync_step(H.make_sync(H.SyncPlan.from_config(hfl)))
    return sync.lower(_state(hfl))


def _lowered(kind):
    return _train_lowered() if kind == "train" else _sync_lowered(*kind)


def _names(op_name):
    """Scope and transform names on an op_name path (the primitive, the
    last component, left out), as the benchmark's reader splits them."""
    comps = op_name.split(";")[0].split("/")[:-1]
    return [t for c in comps for t in re.findall(r"[^()]+", c)]


def _entry(hlo_text):
    """(opcode, operands, op_name) of each ENTRY instruction."""
    body = hlo_text[hlo_text.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    out = []
    for line in body.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (?:\(.*?\)|\S+) "
                     r"([\w\-]+)\((.*?)\)", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2),
                        name.group(1) if name else ""))
    return out


def _strip(hlo_text):
    """Compiled HLO without what only names the source: the metadata, the
    stack-frame tables, and instruction numbering (renamed in order of
    first appearance)."""
    t = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", hlo_text,
               flags=re.S)
    t = re.sub(r",? ?metadata=\{[^}]*\}", "", t)
    seen = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: seen.setdefault(m.group(0), f"%v{len(seen)}"), t)


# a fusion keeps the name of its root, so a scope whose ops all fuse into
# another scope's (the local mean into the downlink drift) leaves no name
@pytest.mark.parametrize("kind,scopes", [
    ("train", TRAIN_SCOPES + ("attention",)),
    (("sparse", "topk"), ("sync.select", "sync.compact", "sync.merge")),
    (("sparse", "fused"), ("sync.select", "sync.merge")),
    (("dense", "topk"), ("sync.exchange",)),
], ids=["train", "topk", "fused", "dense"])
def test_compiled_programs_carry_the_scopes(kind, scopes):
    text = _lowered(kind).compile().as_text()
    names = {n for op in re.findall(r'op_name="([^"]*)"', text)
             for n in _names(op)}
    assert set(scopes) <= names
    if kind == "train":
        # attention runs in both passes
        assert any("transpose" in _names(op) and "attention" in _names(op)
                   for op in re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("mode,impl", SYNCS, ids=lambda x: x)
def test_every_sync_instruction_has_one_scope(mode, impl):
    text = _sync_lowered(mode, impl).as_text(dialect="hlo", debug_info=True)
    for opcode, _, op_name in _entry(text):
        # parameters, constants, their splats and the output tuple do no
        # work
        if opcode in ("parameter", "constant", "tuple") or (
                opcode == "broadcast" and not op_name):
            continue
        got = [n for n in _names(op_name) if n in SYNC_SCOPES]
        assert len(got) == 1, (opcode, op_name)


def test_train_instructions_have_one_scope():
    text = _train_lowered().as_text(dialect="hlo", debug_info=True)
    scoped = 0
    for _, _, op_name in _entry(text):
        got = [n for n in _names(op_name) if n in TRAIN_SCOPES]
        assert len(got) <= 1, op_name
        scoped += bool(got)
    assert scoped > 100


@pytest.mark.parametrize("kind", ["train"] + SYNCS, ids=str)
def test_scopes_leave_the_compiled_program_unchanged(kind, monkeypatch):
    scoped = _lowered(kind).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lowered(kind).compile().as_text()
    scopes = SYNC_SCOPES + TRAIN_SCOPES
    assert any(n in scoped for n in scopes)
    assert not any(n in bare for n in scopes)
    assert _strip(scoped) == _strip(bare)


# ---------------------------------------------------------------------------
# Host spans on the profiler's clock
# ---------------------------------------------------------------------------


def _run(steps, period=2):
    hfl = _hfl()
    train = jax.jit(H.make_cluster_train_step(make_loss_fn(CFG), OPT,
                                              constant_lr(0.1)))
    sync = H.jit_sync_step(H.make_sync(H.SyncPlan.from_config(hfl)))
    seen = []

    def batches():
        for i in range(steps):
            yield _batch(hfl, i)

    state = schedule.run_hfl(_state(hfl), train, sync, batches(), period,
                             steps, lambda t, s, loss: seen.append(t))
    return jax.device_get(state), seen


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    (path,) = list(trace_dir.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(path))
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("hfl."):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name,
                                      dict(ev.stats).get("step_num")))
    return sorted(spans)


def test_run_hfl_spans_nest_and_leave_the_state_alone(tmp_path):
    _run(4)  # compile outside the trace
    plain, _ = _run(4)
    with jax.profiler.trace(str(tmp_path)):
        traced, seen = _run(4)
    assert seen == [0, 1, 2, 3]
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(traced)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    spans = _host_spans(tmp_path)
    names = [s[2] for s in spans]
    assert names.count("hfl.run") == 1
    runs = [s for s in spans if s[2] == "hfl.run"]
    steps = [s for s in spans if s[2] == "hfl.step"]
    assert [s[3] for s in steps] == [0, 1, 2, 3]
    inside = lambda s, o: o[0] <= s[0] and s[1] <= o[1]
    assert all(inside(s, runs[0]) for s in spans)
    want = {0: ["hfl.round", "hfl.batch", "hfl.train", "hfl.on_step"],
            1: ["hfl.batch", "hfl.train", "hfl.sync", "hfl.on_step"]}
    for st in steps:
        kids = [s[2] for s in spans
                if s[2] not in ("hfl.run", "hfl.step") and inside(s, st)]
        assert kids == want[st[3] % 2]


def test_host_spans_record_chrome_events_when_on():
    from repro.obs import ObsConfig, make_telemetry

    tele = make_telemetry(ObsConfig())
    with tele.host_span("hfl.step", step=7):
        with tele.host_span("hfl.train"):
            pass
    ev = [e for e in tele.tracer.to_chrome()["traceEvents"]
          if e.get("ph") == "X"]
    assert [e["name"] for e in ev] == ["hfl.train", "hfl.step"]
    assert ev[1]["args"] == {"step": 7}
    off = make_telemetry(dataclasses.replace(ObsConfig(), host_spans=False))
    with off.host_span("hfl.train"):
        pass
    assert not off.tracer.events


def test_compile_cache_keys_on_the_scopes(monkeypatch, tmp_path):
    # a program loaded from the persistent cache must carry its own scope
    # names, not those of an earlier build of the same ops
    from repro.utils.compile_cache import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        use_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)


def test_compile_cache_key_leaves_out_the_checkout_path(monkeypatch,
                                                        tmp_path):
    # the key holds the source paths of the metadata: relative to the
    # checkout, a checkout at another path finds the same programs
    from repro.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = [getattr(jax.config, f) for f in flags]
    try:
        compile_cache.use_compile_cache()
        text = _sync_lowered("dense", "topk").as_text(debug_info=True)
    finally:
        for f, v in zip(flags, before):
            jax.config.update(f, v)
    root = compile_cache.CACHE_DIR.parent
    assert '"src/repro/core/hfl.py"' in text
    assert str(root) not in text
