"""Device idle share: 1 - busy / traced window, mean over the cell's
chips, in %."""


def read(run):
    tr = run["trace"]
    if not tr["busy_s"]:
        return None
    busy = sum(tr["busy_s"].values()) / len(tr["busy_s"])
    return 100.0 * (1.0 - busy / tr["window_s"])
