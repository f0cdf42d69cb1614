"""Mean client latency minus the mean of the jobs' ``latency_s`` as the
scheduler recorded it: what the client adds, the status-poll sleep and the
result fetch."""

from layers._history import mean_client_ms, window_jobs


def read(obs):
    jobs = window_jobs(obs)
    if jobs is None:
        return None
    served = 1e3 * sum(float(j["latency_s"]) for j in jobs) / len(jobs)
    return mean_client_ms(obs) - served
