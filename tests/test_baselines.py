"""The paper's baselines (binary joins, then aggregate; pre-aggregation
before each join) answer every catalog query exactly as the brute-force
oracle does, so the paper tables compare JOIN-AGG against right answers.

FOFGROUP is left out: its group attribute also joins, which the bare
oracle refuses without the planner's column copy."""
import pytest

from repro.baselines.binary_join import binary_join_agg
from repro.baselines.preagg import preagg_join_agg
from repro.data.queries import CYCLIC, REAL, SKEWED
from repro.relational.oracle import oracle_joinagg

_CATALOG = {
    **REAL, **SKEWED, **{k: v for k, v in CYCLIC.items() if k != "FOFGROUP"}
}


@pytest.mark.parametrize("name", sorted(_CATALOG))
@pytest.mark.parametrize("baseline", [binary_join_agg, preagg_join_agg],
                         ids=["binary", "preagg"])
def test_baseline_matches_oracle(baseline, name):
    db, q = _CATALOG[name](300, seed=0)
    got, stats = baseline(q, db)
    want = oracle_joinagg(q, db)
    assert want, name  # the query has groups to compare
    assert got == want
    assert stats.max_intermediate_rows > 0
