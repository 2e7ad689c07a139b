"""flow_ms.single: host clock around each pick's flow call, inside the
harness's flow wrapper, averaged over the window's rounds."""


def read(run):
    if not run.rounds:
        return None
    return 1e3 * sum(r.flow_s for r in run.rounds) / len(run.rounds)
