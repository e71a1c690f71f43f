"""tools/aot_step_ops.py reads an optimized HLO module: which operations
materialise a layer of the slot cache or more. Fed a recorded excerpt of
the step as it compiled before PR 25 (no compiler needed here; the
compile itself is tests/test_tpu_compile.py's)."""

import os

import pytest

from tools.aot_step_ops import big_ops, render

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER = 144 * 383 * 16 * 64
PADDED = "{4,3,2,1,0:T(8,128)(2,1)}"
STORED = "{2,4,3,1,0:T(8,128)(2,1)}"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "aot_step_ops_parent_step.hlo.txt")) as f:
        return f.read()


@pytest.mark.parametrize(
    "least, names",
    [
        pytest.param(LAYER, [
            "constant_dynamic-slice_fusion.13", "copy.34",
            "broadcast_select_fusion.3", "copy.38",
            "constant_dynamic-update-slice_fusion.4", "custom-call.4",
            "convert_element_type.42", "copy.56",
        ], id="a_layer_or_more"),
        pytest.param(24 * LAYER, [
            "constant_dynamic-update-slice_fusion.4", "custom-call.4",
            "copy.56",
        ], id="the_whole_cache"),
    ],
)
def test_big_ops_lists_what_materialises_the_cache(recorded, least, names):
    found = big_ops(recorded, least)
    assert [op["name"] for op in found["ops"]] == names
    # The selects inside the fusion are not materialised, so not listed.
    assert not any(op["name"].startswith("select_n") for op in found["ops"])


def test_big_ops_keeps_layout_scope_and_target(recorded):
    found = big_ops(recorded, LAYER)
    by_name = {op["name"]: op for op in found["ops"]}
    select = by_name["broadcast_select_fusion.3"]
    assert select["opcode"] == "fusion"
    assert select["computation"].startswith("wide.wide.region_0")
    assert select["scope"].endswith("kv.write/jit(_where)/select_n")
    # Both arrays of a fusion's result tuple, in the padded layout the
    # body was put in; the layer slice still in the stored one.
    assert [(s[1], s[2]) for s in select["shapes"]] == [
        ((1, 144, 383, 16, 64), PADDED)] * 2
    assert by_name["copy.34"]["shapes"][0][2] == PADDED
    assert by_name["constant_dynamic-slice_fusion.13"]["shapes"][0][2] == (
        "{2,4,3,1,0:T(8,128)(2,1)S(1)}")
    assert by_name["custom-call.4"]["opcode"] == "custom-call AllocateBuffer"
    assert by_name["copy.56"]["shapes"] == [
        ("bf16", (24, 144, 383, 16, 64), STORED, 24 * LAYER)]
    # The parameter, the tuples, the while that carries them and the
    # reads out of it only rename a buffer.
    assert found["aliasing"] == 7
    text = render(found)
    assert "copy.56 (copy) bf16[24,144,383,16,64]" + STORED in text
    assert text.endswith("8 listed, 7 that only rename a buffer")
