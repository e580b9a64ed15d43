"""The closed loop, end to end on the CPU at a tiny size."""

from chipbench.tests.helpers import E2E, assert_contract_shape, run_tiny, \
    tiny_root


def test_closed_loop_run_is_correct(tmp_path):
    result, lines = run_tiny(tiny_root(tmp_path, loop="closed"))
    assert_contract_shape(result, [n for n, _ in E2E])
    assert result["correct"], lines
    assert result["failed"] == 0
    assert result["metrics"]["qps"]["value"] > 0
    assert 0.8 <= result["metrics"]["recall_at_10"]["value"] <= 1.0
    assert result["loadgen"]["compiles_in_window"] == 0
    assert [ln.split(":")[0] for ln in lines] == [
        f"check {name}" for name in result["checks"]]
