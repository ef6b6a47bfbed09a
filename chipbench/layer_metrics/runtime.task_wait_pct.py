"""Share of the map and reduce tasks' time, submit to done, that they spent
waiting for a worker of the pool: the sum of submit-to-start over the sum of
submit-to-done, from the ``pool:<fn>`` spans the runtime's collector records
on the driver's side while the trace is on (``layers.runtime`` of the
loader's stats). The tasks are the shuffle's own (``shuffle_*``); a cell
whose tasks all run in set-up, before any session, has nothing to read."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    by_fn = (layers.get("runtime") or {}).get("by_fn") or {}
    tasks = [c for fn, c in by_fn.items() if fn.startswith("shuffle_")]
    wait_s = sum(c["wait_s"] for c in tasks)
    total_s = wait_s + sum(c["run_s"] for c in tasks)
    if not total_s:
        return None
    return 100.0 * wait_s / total_s
