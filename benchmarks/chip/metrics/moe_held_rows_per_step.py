"""Model step: rows of work for the held experts per decode step, from
the program's counters: the (row, expert) choices that fell on this
chip's routed experts, summed over layers, over the decode steps of the
run (``ServeProgram.result()``'s ``moe_held_rows`` and ``steps``).  A
program without the counter reports nothing."""


def read(run):
    res = run.win.result
    if "moe_held_rows" not in res or not res.get("steps"):
        return None
    return res["moe_held_rows"] / res["steps"]
