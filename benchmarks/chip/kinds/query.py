"""Query traffic: one closed-loop client repeating the cell's query
through ``Session.query``.  A sample of the answers, drawn from the
seed, is checked afterwards against the reference on the registered
tables.  End-to-end: ``queries_per_s`` (answers over the window) and
``query_p95_ms`` (the tail of every query's latency)."""
from __future__ import annotations

import loops


def make(mix: dict, ctx) -> "QueryLoop":
    return QueryLoop(mix, ctx)


class QueryLoop(loops.ClosedLoop):
    span_name = "query"

    def __init__(self, mix, ctx):
        from repro.serve.session import Session

        super().__init__(mix)
        self.query, self.tables = ctx.query, ctx.tables
        self.session = Session(ctx.server)
        self.kept = loops.Reservoir(mix["check_sample"], ctx.seed)

    def request(self):
        return self.session.query(self.query)

    def keep(self, answer) -> None:
        self.kept.offer(answer)

    def answers(self) -> list[tuple[dict, object]]:
        return [(self.tables, a) for a in self.kept.items]

    def end_to_end(self) -> dict[str, float]:
        return {
            "queries_per_s": len(self.latencies) / self.elapsed,
            "query_p95_ms": loops.p95_ms(self.latencies),
        }
