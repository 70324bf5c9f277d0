"""Mean seconds a request waited in ``DeadlineScheduler``'s queue before
its batch was dispatched, from the program's
``mafl_scheduler_queue_wait_seconds`` histogram (its sum and count over
the window), in milliseconds."""


def read(obs):
    n = obs.counters.get("queue_wait_count")
    if not n:
        return None
    return 1e3 * obs.counters["queue_wait_s_sum"] / n
