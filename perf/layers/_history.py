"""The window's jobs in the scheduler's history (``system.queries`` through
the client's ``GetHistory``): this session's completed jobs submitted inside
the window. Matched by time, since the client does not hand out job ids; a
reader gets nothing unless they are exactly the window's queries."""


def window_jobs(obs):
    jobs = obs["jobs"]
    done = [r for r in obs["queries"] if r["error"] is None]
    if not jobs or not done:
        return None
    lo, hi = obs["window_t0"], obs["window_t1"]
    mine = [
        j for j in jobs
        if j.get("status") == "completed"
        and j.get("session_id") == obs["session_id"]
        and lo <= float(j.get("submitted_s", 0)) <= hi
    ]
    if len(mine) != len(done):
        return None
    return mine


def mean_client_ms(obs) -> float:
    done = [r for r in obs["queries"] if r["error"] is None]
    return 1e3 * sum(r["t1"] - r["t0"] for r in done) / len(done)


def attempt_cost(obs, jobs, key: str) -> float:
    """The sum of one key of the cost vector over the task attempts of
    ``jobs`` (the rows nest it under ``cost``)."""
    ids = {j["job_id"] for j in jobs}
    return sum(
        float((a.get("cost") or a).get(key, 0.0))
        for a in obs["attempts"] if a.get("job_id") in ids
    )
