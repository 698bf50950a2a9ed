"""Serial host work per engine loop iteration: the mean
``engine.iteration`` span less the time its ``*.fetch`` and
``bench.*`` descendants cover (waits on the device and the benchmark's
own work), in ms."""


def read(rec):
    tr = rec["trace"]
    return None if tr is None else tr["loop_host_ms"]
