"""The benchmark's load generator: a corrected copy of
``paddle_tpu/loadgen`` (listed in PERF.md for a later PR to delete the
original). What changed, and why:

- a request is timed from when it was DUE, not from when it was sent, so
  the wait a stall imposes on later requests counts; how late the
  generator itself ran (``lag_s``) is reported beside it;
- lengths come from log-normal, log-uniform or uniform laws with clips,
  not from uniform ranges only;
- a closed loop (N clients, each sends its next request when the last
  one ended) beside the open loop;
- a percentile is given only with ten samples beyond it;
- it runs as a CHILD PROCESS that never imports JAX (``client.py``), so
  its threads share no interpreter lock with the engine's thread.

``schedule.py`` is pure: (parameters, seed) -> the same requests, byte
for byte, with a digest to prove it.
"""
