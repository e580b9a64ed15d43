"""`trevi64k` is `trevi4096` at twice the rows: the same deployment, index,
engine, limits and data, cut less far from the paper's 99,100 rows."""

from chipbench.spec import Spec
from chipbench.tests.helpers import REPO


def test_trevi64k_differs_from_trevi4096_in_scale_only():
    spec = Spec(REPO)
    big, small = spec.config("trevi64k"), spec.config("trevi4096")
    assert (big["name"], big["n"], big["d"]) == ("trevi64k", 65_536, 4096)
    assert set(big) == set(small)
    for key in set(big) - {"name", "n", "reduced"}:
        assert big[key] == small[key], key
    assert big["n"] <= big["paper_n"] == 99_100
    assert set(big["reduced"]) == {"n"}
    cell = spec.workload("trevi64k.mixedp")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("trevi64k", "mixedp", 1)
    # four segments of whole sublane tiles, as trevi4096's
    segments = big["index"]["num_segments"]
    assert big["n"] % (8 * segments) == 0
