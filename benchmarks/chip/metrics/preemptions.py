"""KV memory: requests the engine preempted in the window
(``ServingEngine.preemptions``)."""


def read(v):
    return v.counters["preemptions"]
