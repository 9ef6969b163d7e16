"""Share of the window the co-location executor spent outside the jobs'
``step_fn`` calls: batch preparation, loss readback, switching between jobs
(1 - the jobs' summed step times in the window / the window), in percent."""


def read(record):
    stepped = sum(sum(j["step_s"]) for j in record["jobs"])
    return 100.0 * (1.0 - stepped / record["window_s"])
