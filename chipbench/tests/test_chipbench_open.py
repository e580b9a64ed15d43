"""The open loop, end to end on the CPU at a tiny size."""

from chipbench.tests.helpers import E2E, assert_contract_shape, run_tiny, \
    tiny_root


def test_open_loop_run_is_correct(tmp_path):
    result, lines = run_tiny(tiny_root(tmp_path, loop="open"))
    assert_contract_shape(result, [n for n, _ in E2E])
    assert result["correct"], lines
    # every arrival of the window was offered: 40 q/s in bursts of 4
    assert result["attempted"] >= 40
    assert result["loadgen"]["late_max_ms"] >= 0
