"""The serving program's own trace: scheduler spans, model scopes and the
names of its compiled programs.

A tiny phi3 with an int8 paged KV cache and chunked prefill serves
staggered requests of several prompt lengths twice, once under
``jax.profiler`` and once without it:

* every ``sched.*`` span nests in a ``sched.tick`` span, and the span
  args are what the scheduler handed the engine (fill levels of the
  decode rows, start and prompt tokens of each prefill chunk);
* the served tokens are the same with the profiler on and off;
* ``hlo_op_scopes`` finds the model step's scopes in the compiled
  decode program, and lets an instruction without metadata inherit its
  first scoped operand's scope;
* the compiled programs are the XLA modules ``jit_decode_step`` and
  ``jit_prefill_chunk_at``, and the profile keeps each program's HLO
  with its scopes, which the benchmark's reader finds;
* the benchmark's trace reductions pass their hand-worked fixtures.
"""
import glob
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models.api import build
from repro.models.common import QuantConfig
from repro.models.transformer import SCOPES
from repro.serve import SPANS, Request, SamplingParams, ServeEngine
from repro.dist.hlo_analysis import hlo_op_scopes

BENCH = Path(__file__).resolve().parents[1] / "bench"
LENS = (5, 12, 20, 9)          # one chunk, two, three (chunk 8), two
CHUNK, PAGE, SLOTS, MAX_LEN = 8, 4, 2, 32


def _engine():
    cfg = REGISTRY["phi3-mini-3.8b"].tiny(dtype="float32").with_quant(
        QuantConfig(mode="fake", n_bits=8, act_bits=8))
    api = build(cfg)
    return ServeEngine(api, api.init(jax.random.PRNGKey(0)),
                       kv_quant_bits=8, page_size=PAGE, prefill_chunk=CHUNK)


def _requests(vocab):
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (len(LENS), max(LENS)), 0, vocab),
        np.int32)
    return [Request(uid=i, inputs={"tokens": jnp.asarray(toks[i:i + 1, :n])},
                    sampling=SamplingParams(max_new_tokens=4), arrival=i)
            for i, n in enumerate(LENS)]


def _profile(trace_dir) -> str:
    return sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]


def _spans(trace_dir):
    """Program spans of the newest profile: (name, start, end, args)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(_profile(trace_dir))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in SPANS]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One untraced and one traced serve of the same requests, with what
    the scheduler handed the engine in the traced one."""
    eng = _engine()
    vocab = eng.api.cfg.vocab
    plain = eng.serve(_requests(vocab), n_slots=SLOTS, max_len=MAX_LEN)
    given = {"decode": [], "chunk": []}
    decode, chunk = eng.decode, eng.prefill_chunk_at

    def logged_decode(tokens, state, index):
        given["decode"].append(np.asarray(index))
        return decode(tokens, state, index)

    def logged_chunk(batch, state, slot, start):
        given["chunk"].append((int(slot), int(start),
                               int(batch["tokens"].shape[1])))
        return chunk(batch, state, slot, start)

    eng.decode, eng.prefill_chunk_at = logged_decode, logged_chunk
    sched = eng.make_scheduler(_requests(vocab), n_slots=SLOTS,
                               max_len=MAX_LEN)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        traced = sched.run(_requests(vocab))
    finally:
        jax.profiler.stop_trace()
    eng.decode, eng.prefill_chunk_at = decode, chunk
    return dict(eng=eng, sched=sched, plain=plain, traced=traced,
                given=given, spans=_spans(trace_dir),
                profile=_profile(trace_dir))


def test_spans_nest_in_ticks(served):
    spans, sched = served["spans"], served["sched"]
    ticks = [s for s in spans if s[0] == "sched.tick"]
    assert [s[3]["tick"] for s in ticks] == list(range(sched.tick))
    names = {s[0] for s in spans}
    assert names == set(SPANS), set(SPANS) - names
    for name, a, b, _ in spans:
        if name != "sched.tick":
            assert any(t[1] <= a and b <= t[2] for t in ticks), name


def test_tick_args_count_the_tick(served):
    """A tick's ``chunks`` counts its ``sched.prefill_chunk`` spans and its
    ``live`` the rows of its ``sched.decode``."""
    spans = served["spans"]
    for _, a, b, args in (s for s in spans if s[0] == "sched.tick"):
        inside = [s for s in spans if a <= s[1] and s[2] <= b]
        chunks = [s for s in inside if s[0] == "sched.prefill_chunk"]
        decodes = [s[3] for s in inside if s[0] == "sched.decode"]
        assert args["chunks"] == len(chunks)
        assert args["live"] == sum(d["live"] for d in decodes)


def test_decode_span_args_are_what_decode_was_given(served):
    total_len = served["sched"].total_len
    got = [s[3] for s in served["spans"] if s[0] == "sched.decode"]
    given = served["given"]["decode"]
    assert len(got) == len(given) > 0
    for args, index in zip(got, given):
        fills = index[index != total_len - 1] + 1
        assert args["rows"] == len(index)
        assert args["live"] == len(fills)
        assert args["fill_sum"] == int(fills.sum())
        assert args["fill_max"] == int(fills.max())


def test_chunk_span_args_follow_the_chunk_plan(served):
    """Each chunk's start and width are what the engine was given, and
    its real tokens what the plan leaves of the prompt: whole chunks
    but the last, which holds the prompt's remainder."""
    got = [s[3] for s in served["spans"] if s[0] == "sched.prefill_chunk"]
    given = served["given"]["chunk"]
    assert [(a["slot"], a["start"], a["width"]) for a in got] == given
    for uid, n in enumerate(LENS):
        mine = [a for a in got if a["uid"] == uid]
        starts = list(range(0, n, CHUNK)) if n > CHUNK else [0]
        assert [a["start"] for a in mine] == starts
        assert [a["real"] for a in mine] == [min(CHUNK, n - s)
                                             for s in starts]
    waits = [s[3]["uid"] for s in served["spans"]
             if s[0] == "sched.first_token_wait"]
    assert sorted(waits) == list(range(len(LENS)))


def test_tokens_same_with_profiler_on_and_off(served):
    assert [r.tokens for r in served["traced"]] == \
        [r.tokens for r in served["plain"]]


def _decode_args(eng, sched):
    n = sched.n_slots
    return (jax.ShapeDtypeStruct((n, 1), jnp.int32), sched.state,
            jax.ShapeDtypeStruct((n,), jnp.int32))


def _pool_shapes(state):
    """Every paged pool leaf's shape, and one layer's (with and without
    the stack dim)."""
    shapes = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if "pages" in jax.tree_util.keystr(path):
            s = tuple(leaf.shape)
            shapes |= {s, s[1:], (1,) + s[1:]}
    return shapes


_RESULT = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]",
                     re.M)


def _moves_under_kv_scopes(text, state):
    """Ops scoped ``kv_slice`` / ``kv_update`` whose result is a whole
    layer's page pool or the whole stack: the per-layer moves the layer
    walk no longer makes (the pool is read and written in place)."""
    scopes = hlo_op_scopes(text, SCOPES)
    pools = _pool_shapes(state)
    return [name for name, dims in _RESULT.findall(text)
            if scopes.get(name) in ("kv_slice", "kv_update")
            and tuple(int(d) for d in dims.split(",") if d) in pools]


def test_op_scopes_name_the_decode_steps_parts(served):
    eng, sched = served["eng"], served["sched"]
    text = eng.compiled_text("decode", *_decode_args(eng, sched))
    scopes = hlo_op_scopes(text, SCOPES)
    found = set(scopes.values())
    assert {"kv_write", "attn_core", "mlp"} <= found, found
    assert found <= set(SCOPES) | {"unscoped"}, found
    assert not _moves_under_kv_scopes(text, sched.state)


def test_compiled_module_names(served):
    eng, sched = served["eng"], served["sched"]
    decode = eng.compiled_text("decode", *_decode_args(eng, sched))
    batch = {"tokens": jax.ShapeDtypeStruct((1, CHUNK), jnp.int32)}
    chunk = eng.compiled_text(
        "prefill_chunk", batch, sched.state,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    assert decode.startswith("HloModule jit_decode_step,")
    assert chunk.startswith("HloModule jit_prefill_chunk_at,")


HLO = """\
HloModule jit_decode_step, is_scheduled=true

%fused_computation.1 (param_0: s8[4,8]) -> s8[8] {
  %param_0 = s8[4,8]{1,0} parameter(0)
  ROOT %slice.2 = s8[8]{0} slice(%param_0), slice={[0:1], [0:8]}
}

ENTRY %main.9 (p.1: s8[4,8], i.2: s32[]) -> s8[8] {
  %p.1 = s8[4,8]{1,0} parameter(0)
  %i.2 = s32[] parameter(1)
  %fusion.3 = s8[8]{0} fusion(%p.1, %i.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode_step)/while/body/closed_call/kv_slice/dynamic_slice" stack_frame_id=3}
  %copy.4 = s8[8]{0:T(128)} copy(%fusion.3)
  %copy.5 = s8[8]{0} copy(%copy.4)
  %add.6 = s32[] add(%i.2, %i.2), metadata={op_name="jit(decode_step)/while/body/add"}
  %copy.7 = s32[] copy(%add.6)
  %tuple.8 = (s32[], s8[8]) tuple(%add.6, %i.2), metadata={op_name="jit(decode_step)/mlp/attn_core/tuple"}
  ROOT %packed_matmul.9 = s8[8]{0} custom-call(%p.1, %copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/mlp/jit(packed_matmul)/packed_matmul/pallas_call"}
}
"""


def test_hlo_op_scopes_inherit_rule():
    """Scope from the innermost documented name in ``op_name``; without
    metadata, from the first operand that has a scope (transitively);
    metadata without a documented name is ``unscoped``."""
    got = hlo_op_scopes(HLO, SCOPES)
    assert got["fusion.3"] == "kv_slice"
    assert got["copy.4"] == got["copy.5"] == "kv_slice"
    assert got["add.6"] == got["copy.7"] == "unscoped"
    assert got["tuple.8"] == "attn_core"
    assert got["packed_matmul.9"] == "mlp"
    assert got["p.1"] == got["param_0"] == "unscoped"


def test_profile_keeps_the_programs_scopes(served):
    """The benchmark reads op scopes from the HLO a profile keeps of each
    program it ran, by the program's own rule."""
    sys.path.insert(0, str(BENCH))
    import programtrace
    with open(served["profile"], "rb") as f:
        texts = programtrace._module_texts(f.read())
    decode = [t for name, t in texts.items()
              if name.startswith("jit_decode_step(")]
    assert decode, sorted(texts)
    found = set(hlo_op_scopes(decode[0], SCOPES).values())
    assert {"kv_write", "attn_core", "mlp"} <= found, found
    assert not _moves_under_kv_scopes(decode[0], served["sched"].state)


@pytest.mark.parametrize("script", ["selfcheck.py", "selfcheck_program.py"])
def test_bench_selfcheck(script):
    out = subprocess.run([sys.executable, str(BENCH / script)],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
