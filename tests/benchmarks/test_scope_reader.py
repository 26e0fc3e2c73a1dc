"""`readers/scope_device_ms.py` on hand-made traces: XSpace text protos
whose operations carry their scope path where a v5e trace carries it (a
`tf_op` stat on the event's metadata), or only in the program's HLO
proto; and the four metrics that read it."""

import types

import pytest

from benchmarks.files import Manifest, load_py

R = load_py("readers", "scope_device_ms")
#: the serving cells whose per-layer metrics no accepted test pins by
#: name (`test_hybrid_config.py` and `test_latent_config.py` pin theirs)
SERVING = ["neox-3.6b.serve-chat", "smallthinker-21b.serve-mixed"]
US = 1000  # ns


def _escape(data: bytes) -> str:
    return "".join("\\%03o" % b for b in data)


def _msg(*fields) -> bytes:
    """A protobuf message from (field number, int or bytes) pairs."""
    def varint(n):
        out = b""
        while True:
            low, n = n & 0x7F, n >> 7
            out += bytes([low | (0x80 if n else 0)])
            if not n:
                return out
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        else:
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def _hlo_proto(op_names):
    """HloProto holding one computation of instructions {name: op_name}."""
    insts = [(2, _msg((1, name.encode()), (7, _msg((2, op.encode())))))
             for name, op in op_names.items()]
    return _msg((1, _msg((1, b"m"), (3, _msg((1, b"main"), *insts)))))


def _trace(tmp_path, modules, ops, paths_on_metadata=True, protos=None):
    """`modules` [(name, start_us, end_us)] and `ops` [(text, start_us,
    end_us, scope path or None)] on /device:TPU:0; each operation's path a
    `tf_op` stat of its metadata (with the program id of the run that
    holds it), or none; `protos` {program id: HloProto bytes} on the
    /host:metadata plane. Returns a ctx whose trace_file() is the file."""
    meta, events = {}, {"XLA Modules": [], "XLA Ops": []}

    def mid(text, stats=""):
        if text not in meta:
            meta[text] = (len(meta) + 1, stats)
        return meta[text][0]
    for name, a, b in modules:
        events["XLA Modules"].append((mid(name), a, b))
    for text, a, b, path in ops:
        pid = next((int(n.rsplit("(", 1)[1][:-1]) for n, x, y in modules
                    if x <= a and b <= y), 0)
        stats = f'stats {{ metadata_id: 2 uint64_value: {pid} }}'
        if path is not None and paths_on_metadata:
            stats += f' stats {{ metadata_id: 1 str_value: "{path}:" }}'
        events["XLA Ops"].append((mid(text, stats), a, b))
    lines = "".join(
        f'lines {{ id: {i} name: "{line}" timestamp_ns: 0 '
        + "".join(f"events {{ metadata_id: {m} offset_ps: {a * US * 1000} "
                  f"duration_ps: {(b - a) * US * 1000} }} "
                  for m, a, b in evs) + "} "
        for i, (line, evs) in enumerate(events.items()))
    md = "".join(f'event_metadata {{ key: {m} value {{ id: {m} name: '
                 f'"{text}" {stats} }} }} '
                 for text, (m, stats) in meta.items())
    space = (f'planes {{ id: 1 name: "/device:TPU:0" {lines} {md} '
             'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
             'stat_metadata { key: 2 value { id: 2 name: "program_id" } } '
             '} ')
    if protos:
        space += 'planes { id: 2 name: "/host:metadata" ' + "".join(
            f'event_metadata {{ key: {pid} value {{ id: {pid} name: '
            f'"m({pid})" stats {{ metadata_id: 1 bytes_value: '
            f'"{_escape(proto)}" }} }} }} ' for pid, proto in protos.items()) \
            + 'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } } } '
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(space))
    return types.SimpleNamespace(trace_file=lambda: str(path))


KV = "jit(_decode_fn)/full attention/kv write/vmap(vmap())/scatter"
ATTN = "jit(_decode_fn)/full attention/dot_general"
EXPERTS = "jit(_decode_fn)/moe experts/moe down/dot_general"


def _decode_ops(at, attn_us):
    """One decode run's operations from `at`: attention, a cache write
    the compiler made a while loop of (its body unnamed), the experts, the
    wait for a prefetch the compiler started."""
    return [("%fusion.1 = f32[8] fusion()", at + 1, at + 1 + attn_us, ATTN),
            ("%while.1 = (s32[]) while()", at + 40, at + 60, KV),
            ("%dynamic-update-slice.1 = bf16[8] dynamic-update-slice()",
             at + 41, at + 45, None),
            ("%fusion.9 = bf16[8] fusion()", at + 46, at + 50, None),
            ("%fusion.2 = f32[8] fusion()", at + 61, at + 81, EXPERTS),
            ("%copy-done.1 = bf16[8] copy-done()", at + 82, at + 87, None)]


@pytest.fixture
def decode(tmp_path):
    # four runs: the first begins the slice and the last ends it, so only
    # the two between them are whole; a prefill run between is not read
    runs = [("jit__decode_fn(7)", 0, 100), ("jit__decode_fn(7)", 200, 300),
            ("jit__prefill_fn(8)", 310, 390),
            ("jit__decode_fn(7)", 400, 500), ("jit__decode_fn(7)", 600, 700)]
    ops = _decode_ops(0, 30) + _decode_ops(200, 30) + _decode_ops(400, 10) \
        + _decode_ops(600, 30) \
        + [("%fusion.7 = f32[8] fusion()", 320, 380,
            "jit(_prefill_fn)/full attention/dot_general")]
    return _trace(tmp_path, runs, ops)


def _read(ctx, modules, scopes, without=()):
    return R.read(ctx, None, None, {"modules": modules, "scopes": scopes,
                                    "without": list(without)})


def test_the_while_loops_body_counts_under_the_loops_scope(decode):
    # 4 + 4 us of body a run; the loop itself is not counted with it
    assert _read(decode, ["jit__decode_fn"], ["kv write"]) == \
        pytest.approx(0.008)


def test_without_leaves_the_cache_writes_out_of_attention(decode):
    # whole runs only, divided by their number: (30 + 10) / 2 us
    assert _read(decode, ["jit__decode_fn"], ["full attention"],
                 ["kv write"]) == pytest.approx(0.020)
    assert _read(decode, ["jit__decode_fn"], ["full attention"]) == \
        pytest.approx(0.028)
    assert _read(decode, ["jit__decode_fn"], ["moe experts"]) == \
        pytest.approx(0.020)
    assert _read(decode, ["jit__prefill_fn"], ["full attention"]) == \
        pytest.approx(0.060)
    # the split a run by scope: a wait for a prefetch has a name
    assert R.split(R.runs_in_file(decode.trace_file()), "jit__decode_fn") == \
        pytest.approx({"full attention": 0.020, "moe experts > moe down":
                       0.020, "full attention > kv write": 0.008,
                       "prefetch wait": 0.005})


def test_nothing_to_read_is_none(decode, tmp_path):
    assert _read(decode, ["jit_step"], ["kv write"]) is None
    assert _read(decode, ["jit__decode_fn"], ["latent attention"]) is None
    runs = [("jit__decode_fn(7)", 0, 100), ("jit__decode_fn(7)", 200, 300),
            ("jit__decode_fn(7)", 400, 500)]
    bare = [(t, a, b, None) for t, a, b, _ in _decode_ops(200, 30)]
    sub = tmp_path / "bare"
    sub.mkdir()
    ctx = _trace(sub, runs, _decode_ops(0, 30) + bare
                 + _decode_ops(400, 30), paths_on_metadata=False)
    assert _read(ctx, ["jit__decode_fn"], ["full attention"]) is None
    assert R.read(types.SimpleNamespace(trace_file=lambda: None), None,
                  None, {"modules": ["jit__decode_fn"],
                         "scopes": ["kv write"]}) is None


def test_transformed_components_keep_their_scope(tmp_path):
    runs = [("jit_step(9)", 0, 1000), ("jit_step(9)", 2000, 3000),
            ("jit_step(9)", 4000, 5000)]
    ops = []
    for at in (0, 2000, 4000):
        ops += [("%convolution.1 = f32[8] convolution()", at + 10, at + 110,
                 "jit(step)/transpose(jvp(stage 1))/conv_general_dilated"),
                ("%fusion.3 = f32[8] fusion()", at + 120, at + 170,
                 "jit(step)/jvp(stage 1)/mul"),
                ("%fusion.4 = f32[8] fusion()", at + 200, at + 230,
                 "jit(step)/optimizer update/mul")]
    ctx = _trace(tmp_path, runs, ops)
    assert R.components("jit(step)/transpose(jvp(stage 1))/conv:") == (
        "jit(step)", "stage 1", "conv")
    assert _read(ctx, ["jit_step", "jit_step_under_mesh"], ["stage 1"]) == \
        pytest.approx(0.150)
    assert _read(ctx, ["jit_step"], ["optimizer update"]) == \
        pytest.approx(0.030)
    runs_ = R.runs_in_file(ctx.trace_file())
    assert R.split(runs_, "jit_step") == pytest.approx(
        {"stage 1": 0.150, "optimizer update": 0.030})


def test_the_hlo_proto_names_what_no_tf_op_does(tmp_path):
    runs = [("jit__decode_fn(7)", 0, 100), ("jit__decode_fn(7)", 200, 300),
            ("jit__decode_fn(7)", 400, 500)]
    ops = [(t, a, b, None) for at in (0, 200, 400)
           for t, a, b, _ in _decode_ops(at, 30)]
    proto = _hlo_proto({"fusion.1": ATTN, "while.1": KV, "fusion.2": EXPERTS})
    ctx = _trace(tmp_path, runs, ops, paths_on_metadata=False,
                 protos={7: proto})
    assert _read(ctx, ["jit__decode_fn"], ["kv write"]) == \
        pytest.approx(0.008)
    assert _read(ctx, ["jit__decode_fn"], ["full attention"],
                 ["kv write"]) == pytest.approx(0.030)


@pytest.mark.parametrize("name,cells", [
    ("decode_kv_write_ms", SERVING), ("decode_attention_ms", SERVING),
    ("decode_experts_ms", ["smallthinker-21b.serve-mixed"]),
    ("train_optimizer_ms", ["neox-3.6b.train-t2048"])])
def test_the_four_metrics_read_scopes(name, cells):
    m = Manifest()
    entry = next(e for e in m.doc["per_layer"] if e["name"] == name)
    spec = m.metric_file(name)
    assert spec["reader"] == "scope_device_ms"
    assert entry["workloads"] == cells
    assert (entry["unit"], entry["source"], entry["layer"]) == \
        ("ms", "device_trace", "model step")
    assert set(spec["args"]["scopes"]) <= set(R.VOCABULARY)
