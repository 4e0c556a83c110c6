"""Propagation requests per device launch over the run's analyses:
``device_requests / device_inclusive_launches``, counters of the device
batching layer; how many profiles' columns ride one launch."""


def read(run):
    req = sum(a["summary"]["timings"].get("device_requests", 0.0)
              for a in run.done)
    launches = sum(a["summary"]["timings"].get("device_inclusive_launches", 0.0)
                   for a in run.done)
    return req / launches if launches else None
