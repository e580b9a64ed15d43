"""Inserts under search (insert_share > 0), end to end on the CPU."""

from chipbench.tests.helpers import E2E, assert_contract_shape, run_tiny, \
    tiny_root


def test_churn_run_is_correct(tmp_path):
    result, lines = run_tiny(tiny_root(tmp_path, loop="closed",
                                       insert_share=0.1))
    assert_contract_shape(result, [n for n, _ in E2E])
    assert result["correct"], lines
    assert result["failed"] == 0
    assert result["loadgen"]["inserts"] > 0
