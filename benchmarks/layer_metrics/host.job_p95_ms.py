"""Wall time of a job, `transform(...).collect()`, by the harness's clock:
the 95th percentile where the window holds enough jobs, else the highest
percentile with ten jobs beyond it, else the slowest job. Says which."""


def read(ctx):
    times = sorted(1e3 * (j.end_s - j.start_s) for j in ctx["window"].jobs)
    n = len(times)
    if not n:
        return None
    if n >= 200:
        return {"value": times[int(0.95 * n)], "statistic": "p95", "jobs": n}
    if n > 10:
        q = (n - 11) / n
        return {
            "value": times[n - 11],
            "statistic": f"p{100 * q:.0f}",
            "jobs": n,
        }
    return {"value": times[-1], "statistic": "max", "jobs": n}
