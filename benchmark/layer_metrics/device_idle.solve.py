"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (trace: 1 - busy union / window)."""


def read(run):
    red = run.get("trace")
    if not red or not red["window_s"] or red["busy_s"] <= 0:
        return None
    return 100.0 * red["idle_share"]
