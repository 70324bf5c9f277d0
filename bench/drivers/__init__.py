"""Cell drivers, one per kind of traffic. Each has ``run(run) -> dict``
(set-up, warm-up, the measured window) and ``check(run, outputs)``
(the numbers compared with the reference once the window has closed)."""
