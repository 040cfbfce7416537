"""Tiny traffic for the CPU tests of the cells that ``fakes.TINY`` does
not list: the tests shared by every one-chip cell read each cell's size
from it."""
from perfbench.tests import fakes

fakes.TINY.setdefault("azure_fleet_1e5",
                      {"n_workloads": 24, "w_chunk": 8, "minutes": 60})
