"""remat_share.train: the share of the train step's device time spent on
ops that full remat recomputes (op_names under ``rematted_computation``),
as a percentage of all op time inside the step executions
(bench/scopes.py).  It needs no scope, only the op_names the trace keeps."""
from bench import scopes


def read(ctx):
    st = scopes.read_step(ctx)
    if st is None or not st.named_ns:
        return None
    return 100.0 * st.phase_ns("remat") / st.total_ns()
