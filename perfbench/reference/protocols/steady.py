"""The steady protocol: Poisson arrivals at a fixed offered load, leases
of U[1, T] slots, no queue; a reject is final."""

from perfbench.lib import aggregate


def decide(ref):
    return ref.steady()


def reduce(ref, decisions):
    return aggregate.steady(ref.s, decisions, ref.fleet)
