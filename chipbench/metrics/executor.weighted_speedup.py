"""Work done by the co-located jobs against time-slicing them: the sum over
jobs of (steps in the window x the job's median solo step time), over the
window.  Above 1, sharing the chip did more than running the jobs one after
another would have.  Needs two jobs or more and their solo step times."""


def read(record):
    jobs = record["jobs"]
    if len(jobs) < 2 or any(j.get("solo_step_s") is None for j in jobs):
        return None
    return sum(j["steps"] * j["solo_step_s"] for j in jobs) / record["window_s"]
