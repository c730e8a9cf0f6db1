"""``benchmark/scope_trace.py``'s wire-format reading against messages built
by hand (run by hand: ``python -m pytest benchmark/tests -q``; not tier-1)."""

from benchmark import scope_trace


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(name, opcode, ins_id, scope="", calls=None):
    msg = _field(1, name) + _field(2, opcode) + _field(35, ins_id)
    if scope:
        msg += _field(7, _field(2, scope))
    if calls is not None:
        msg += _field(38, calls)
    return _field(2, msg)


def _hlo_proto() -> bytes:
    fused = (_field(1, "fused_computation.1")
             + _instruction("param_0", "parameter", 10)
             + _instruction("multiply.3", "multiply", 11,
                            "jit(u)/block_2/relayrl_short_conv/mul")
             + _field(5, 7) + _field(6, 11))
    fused_dot = (_field(1, "fused_computation.2")
                 + _instruction("dot.9", "dot", 20, "jit(u)/block_2/conv_out")
                 + _field(5, 8) + _field(6, 20))
    entry = (_field(1, "main")
             + _instruction("fusion.1", "fusion", 1, "jit(u)/stale", calls=7)
             + _instruction("fusion.2", "fusion", 2, "", calls=8)
             + _instruction("copy.4", "copy", 3,
                            "jit(u)/relayrl_short_conv/pad")
             + _instruction("sort.5", "sort", 4)
             + _field(5, 9) + _field(6, 4))
    module = _field(1, "jit_u") + b"".join(
        _field(3, comp) for comp in (fused, fused_dot, entry))
    return _field(1, module)


def test_a_fusion_counts_for_the_scope_of_its_root():
    scopes = scope_trace.instruction_scopes(_hlo_proto())
    assert scopes["fusion.1"] == "jit(u)/block_2/relayrl_short_conv/mul"
    assert scopes["fusion.2"] == "jit(u)/block_2/conv_out"
    assert scopes["copy.4"] == "jit(u)/relayrl_short_conv/pad"
    assert scopes["sort.5"] == ""
    assert scopes["multiply.3"].endswith("relayrl_short_conv/mul")


def test_module_protos_come_from_the_metadata_plane_only():
    proto = _hlo_proto()

    def plane(name, module_name):
        meta = _field(1, 5) + _field(2, module_name) + _field(
            5, _field(1, 1) + _field(6, proto))
        entry = _field(1, 5) + _field(2, meta)
        return _field(1, _field(2, name) + _field(4, entry))

    space = (plane("/host:metadata", "jit_impala_update(123)")
             + plane("/device:TPU:0", "jit_other(9)"))
    got = scope_trace.module_protos(space)
    assert list(got) == ["jit_impala_update(123)"]
    assert got["jit_impala_update(123)"] == proto


def test_instruction_name_of_an_event():
    assert scope_trace.instruction_name(
        "%fusion.77 = (f32[], bf16[8,4]{1,0}) fusion(%p), kind=kLoop"
    ) == "fusion.77"
    assert scope_trace.instruction_name(
        "%relayrl_flash_fwd.5 = bf16[1] custom-call()") == "relayrl_flash_fwd.5"
    assert scope_trace.instruction_name("no instruction here") is None


def test_a_run_without_a_trace_reads_nothing():
    class Run:
        trace = False
        run_dir = "/nonexistent"

    assert scope_trace.of(Run()) is None
    assert scope_trace.ms_per_update(Run(), "relayrl_short_conv") is None
