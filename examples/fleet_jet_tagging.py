"""Multi-tenant fleet serving (beyond the paper — repro.core.tenancy).

Trains a DeepSets jet tagger, deploys it behind a ``FleetServer`` with 4
replica kernels (compiled Pallas on a TPU, interpreted on a CPU), dispatches a
micro-batched event stream sliced across the replicas (scatter/gather),
and reports batched p50/p99 + events/sec with per-replica scatter
accounting, next to the Tier-A modeled multi-tenant schedule on the VEK280
— serial R/latency events/sec plus the pipelined headline: per-replica
initiation interval (II), sustained pipelined events/sec, and the
contended pipelined throughput-frontier target for the deployed replica
count.

    PYTHONPATH=src python examples/fleet_jet_tagging.py [--events 256]
"""
import sys

from repro.launch import serve

if __name__ == "__main__":
    sys.argv = [sys.argv[0], "--model", "deepsets-32", "--replicas", "4",
                "--events", "128", "--train-steps", "150"] + sys.argv[1:]
    serve.main()
