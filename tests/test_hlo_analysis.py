"""The while-aware HLO analyzer that feeds the roofline table."""
import numpy as np
import pytest

from repro.launch.hlo_analysis import (
    Instr,
    _shape_bytes,
    _split_operands,
    _trip_count_from_config,
    analyze_hlo,
    parse_hlo,
    parse_input_output_aliases,
)


def test_shape_bytes():
    assert _shape_bytes("f32[128,512]{1,0}") == 128 * 512 * 4
    assert _shape_bytes("bf16[7,512,128]") == 7 * 512 * 128 * 2
    assert _shape_bytes("(s32[], bf16[4,4])") == 4 + 32
    assert _shape_bytes("pred[10]") == 10
    assert _shape_bytes("f8e4m3fn[100]") == 100
    # tuple with /*index=N*/ comments (real XLA print format)
    assert _shape_bytes("(s32[], f32[2,2], /*index=2*/bf16[4])") == 4 + 16 + 8


def test_shape_bytes_sub_byte_and_fp8_dtypes():
    # every fp8 spelling XLA prints is 1 byte/element
    for dt in ("f8e4m3fn", "f8e5m2", "f8e4m3", "f8e5m2fnuz", "f8e4m3fnuz"):
        assert _shape_bytes(f"{dt}[16,32]") == 16 * 32
    # int4 weights pack two to a byte
    assert _shape_bytes("s4[128,256]{1,0}") == 128 * 256 / 2
    assert _shape_bytes("u4[64]") == 32
    assert _shape_bytes("(s4[8], f8e4m3fn[8], f32[8])") == 4 + 8 + 32
    # unknown dtypes are skipped, not mis-billed
    assert _shape_bytes("token[]") == 0


def test_trip_count_from_backend_config():
    """XLA records statically-known trip counts on the while instruction
    itself; the analyzer must prefer that over cond-constant recovery."""
    line = ('  %w = (s32[], f32[4]) while(%t0), condition=%c, body=%b, '
            'backend_config={"known_trip_count":{"n":"6"}}')
    ins = Instr("w", "(s32[], f32[4])", "while", ["t0"], "", line)
    assert _trip_count_from_config(ins) == 6
    plain = Instr("w", "(s32[], f32[4])", "while", ["t0"], "",
                  "  %w = (s32[], f32[4]) while(%t0), condition=%c")
    assert _trip_count_from_config(plain) is None


def _coll_hlo(op_line: str) -> str:
    return f"""HloModule coll, entry_computation_layout={{()->f32[]}}

%sum (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}}

ENTRY %main (p: f32[64,64]) -> f32[] {{
  %p = f32[64,64] parameter(0)
{op_line}
  %z = f32[] constant(0)
  ROOT %s = f32[] reduce(%o, %z), dimensions={{0,1}}, to_apply=%sum
}}
"""


@pytest.mark.parametrize("op,out_shape,wire", [
    # ring models over a 4-member group, f32[64,64] = 16384 B
    ("all-reduce", "f32[64,64]", 2 * 16384 * 3 / 4),
    ("all-gather", "f32[64,64]", 16384 * 3 / 4),
    ("reduce-scatter", "f32[16,64]", 16384 * 3 / 4),   # in_size-based
    ("all-to-all", "f32[64,64]", 16384 * 3 / 4),
    ("collective-permute", "f32[64,64]", 16384.0),     # no ring factor
])
def test_collective_ring_cost_models(op, out_shape, wire):
    attrs = "replica_groups={{0,1,2,3}}"
    if op == "all-reduce":
        attrs += ", to_apply=%sum"
    line = f"  %o = {out_shape} {op}(%p), {attrs}"
    res = analyze_hlo(_coll_hlo(line))
    assert res["collective_counts"] == {op: 1}
    assert res["collective_wire_bytes_per_device"][op] == pytest.approx(wire)


def test_replica_group_size_bare_and_iota_forms():
    # replica_groups=[2,4] (iota shorthand: 2 groups of 4)
    line = ("  %o = f32[64,64] all-gather(%p), replica_groups=[2,4]<=[8], "
            "dimensions={0}")
    res = analyze_hlo(_coll_hlo(line))
    assert res["collective_wire_bytes_per_device"]["all-gather"] == \
        pytest.approx(16384 * 3 / 4)


def test_parse_input_output_aliases():
    hdr = ("HloModule jit_step, input_output_alias={ {0}: (1, {0}, "
           "may-alias), {1}: (1, {1}, may-alias), {2,0}: (3, {}, "
           "must-alias) }, entry_computation_layout={(f32[2])->f32[2]}")
    assert parse_input_output_aliases(hdr) == [
        ((0,), 1, (0,)), ((1,), 1, (1,)), ((2, 0), 3, ())]
    assert parse_input_output_aliases("HloModule nodonation") == []


def test_donated_jit_shows_aliases_in_compiled_hlo():
    """End-to-end: a donate_argnums jit on CPU really carries
    input_output_alias pairs the donation rule can count."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda c, x: (c + x, x.sum()), donate_argnums=(0,))
    text = f.lower(jnp.zeros((8, 8)), jnp.ones((8, 8))).compile().as_text()
    aliases = parse_input_output_aliases(text)
    assert len(aliases) == 1


def test_split_operands():
    ops = _split_operands("%a, %b.2), kind=kLoop, calls=%c")
    assert ops == ["a", "b.2"]


def test_scan_flops_trip_corrected(subproc):
    """A scan of L matmuls must report L x the single-matmul FLOPs."""
    out = subproc("""
import jax, jax.numpy as jnp
from repro.launch.hlo_analysis import analyze_hlo
L, M, K, N = 7, 64, 128, 96
def f(x, w):
    def body(c, wi):
        return c @ wi, ()
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()
comp = jax.jit(f).lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                        jax.ShapeDtypeStruct((L, K, K), jnp.float32)).compile()
res = analyze_hlo(comp.as_text())
true = 2 * M * K * K * L
ratio = res["flops_per_device"] / true
assert 0.9 < ratio < 1.2, (res["flops_per_device"], true)
print("FLOPS_OK", ratio)
""", devices=1)
    assert "FLOPS_OK" in out


def test_collectives_detected_inside_scan(subproc):
    """FSDP-style: all-gather inside a scanned layer body is multiplied by
    the trip count."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
L, D = 5, 256
def f(x, w):
    def body(c, wi):
        return jnp.tanh(c @ wi), ()
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()
comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, None)),
                                NamedSharding(mesh, P(None, "data", None)))) \
    .lower(jax.ShapeDtypeStruct((8, D), jnp.float32),
           jax.ShapeDtypeStruct((L, D, D), jnp.float32)).compile()
res = analyze_hlo(comp.as_text())
total = res["collective_total_bytes_per_device"]
counts = res["collective_counts"]
# XLA partial-dots the sharded contraction and all-reduces the (8,D)
# activation once per layer iteration: ring wire = 2*8*D*4*(3/4) per trip
per_iter = 2 * 8 * D * 4 * 3 / 4
assert sum(counts.values()) >= L, counts
assert total >= per_iter * L * 0.9, (total, counts)
print("COLL_OK", total, counts)
""", devices=4)
    assert "COLL_OK" in out


def test_parse_hlo_handles_tuple_whiles():
    text = """HloModule test, entry_computation_layout={()->f32[]}

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4,4] get-tuple-element(%p), index=1
  %d = f32[4,4] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4,4]) tuple(%ip, %d)
}

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %p = (s32[], f32[4,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(11)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main () -> f32[] {
  %c = f32[4,4] constant(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,4]) tuple(%z, %c)
  %w = (s32[], f32[4,4]) while(%t0), condition=%cond, body=%body
  %r = f32[4,4] get-tuple-element(%w), index=1
  ROOT %s = f32[] reduce(%r, %z), dimensions={0,1}, to_apply=%body
}
"""
    res = analyze_hlo(text)
    # 11 iterations x (2*4*4*4) dot flops
    assert res["flops_per_device"] == 11 * 2 * 4 * 4 * 4
