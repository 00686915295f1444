"""Observability planes of the live fleet.

``baton_tpu.obs.compute`` is the probe behind the round loop's
per-round compute records (worker → edge → manager → ``rounds.jsonl``
→ fleet ledger → SLO gate → ops console).

``baton_tpu.obs.alerts`` watches those measurements: declarative alert
rules (threshold or multi-window burn-rate) evaluated per node with a
pending→firing→resolved lifecycle into ``alerts.jsonl``, and
``baton_tpu.obs.forensics`` packages the deep evidence a firing
``capture: true`` rule arms — profiler trace, task stacks, loop-lag,
fleet slice, round trace, metric history — into content-addressed
bundles served over HTTP.
"""
