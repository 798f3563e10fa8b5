"""The whole solve program's share of its roofline: the least time the
chip could take for the routine's model work (benchmark/work/, divided
over the chips), the larger of operations over peak FLOP/s and bytes
over peak bytes/s (benchmark/roofs.json), over the device busy time per
solve (trace, averaged over the chips).  For every cell defined so far
the operations bound it (PERF.md)."""


def read(run):
    red = run.get("trace")
    solves = run.get("solves") or 0
    if not red or solves <= 0 or red["busy_s"] <= 0:
        return None
    chips = run["chips"]
    roof = run["roof"]
    least_s = max(run["ops_per_solve"] / chips / roof["flops_per_s"],
                  run["bytes_per_solve"] / chips / roof["bytes_per_s"])
    return 100.0 * least_s / (red["busy_s"] / solves)
