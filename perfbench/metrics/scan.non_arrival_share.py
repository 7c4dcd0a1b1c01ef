"""Percent of the lanes the scan steps over that carry no arrival: the
heartbeats of empty slots, each replica's trailing sentinel and the padding
to the longest replica.  100 x (1 - arrival lanes / lanes scanned), from
the program's counters of the traced call.  Nothing when the program keeps
no counters."""

from perfbench.lib import program


def read(ctx):
    c = program.counters(ctx)
    if not c or not c.get("lanes"):
        return None
    return 100.0 * (1.0 - c["arrival_lanes"] / c["lanes"])
