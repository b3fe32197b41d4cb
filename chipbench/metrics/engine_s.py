"""Engine (``core/agg_engine.BatchedBackend.end_round``): per round, the
``end_round`` span minus the ``fedavg_multi`` spans inside it, in seconds."""


def read(run):
    engine = run.span_s("end_round")
    if engine is None:
        return None
    folds = run.span_s("fedavg_multi") or [0.0] * len(engine)
    return sum(e - f for e, f in zip(engine, folds)) / len(engine)
