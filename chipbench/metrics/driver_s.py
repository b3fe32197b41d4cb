"""Round driver (``api.py``, ``core/topology.run_round``, ``serverless/``,
``store/``, client-side codec encode): per round, the round's wall minus
the engine's ``end_round`` span, in seconds."""


def read(run):
    engine = run.span_s("end_round")
    if engine is None:
        return None
    return sum(r["wall_s"] - e for r, e in zip(run.rounds, engine)) / len(engine)
