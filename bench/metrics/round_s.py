"""round_s: the sum of the window's round durations over their number. A
round runs from the end of the previous flow call (or of its exploration's
prologue) to the end of its pick's flow call; prologues are left out."""


def read(run):
    if not run.rounds:
        return None
    return sum(r.seconds for r in run.rounds) / len(run.rounds)
