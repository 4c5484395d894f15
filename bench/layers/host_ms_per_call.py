"""Host call path: each traced call's span minus the device busy time
inside it on the busiest chip, mean over calls, in ms."""


def read(run):
    tr = run["trace"]
    if not tr["busy_s"]:
        return None
    idle = [s - b for s, b in zip(tr["call_s"], tr["call_busy_s"])]
    return 1e3 * sum(idle) / len(idle)
