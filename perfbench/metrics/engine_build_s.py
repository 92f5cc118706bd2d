"""Host seconds of the graph engine's build: partitioning, the plan
(``GraphEngine.__init__``), the ELL tables (``pagerank_state``) and their
first copy to the chip (host clock)."""


def read(ctx):
    return ctx.setup.get("engine_build_s")
