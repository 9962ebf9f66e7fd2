"""TAPA-CS core: task-graph partitioning/floorplanning/pipelining (C1-C5).

The flow runs through ``repro.compiler.compile()`` (one entry point,
composable passes); the passes call the implementations in
``repro.core.partitioner``, ``repro.core.floorplan`` and
``repro.core.pipelining``.
"""
from .graph import Channel, ResourceProfile, Task, TaskGraph, linear_graph
from .topology import (ALVEO_U55C, ETHERNET_100G, INTER_NODE_10G, PCIE_GEN3X16,
                       TPU_DCN, TPU_ICI, TPU_V5E, Bus, Cluster, DaisyChain,
                       DeviceSpec, Hypercube, Mesh2D, Protocol, Ring, Star,
                       Topology, fpga_ring_cluster, lam, tpu_pod_cluster)
from .partitioner import Partition
from .floorplan import Floorplan, SlotGrid, TPU_POD_GRID, U55C_GRID
from .pipelining import PipelineReport, verify_balanced
from .costmodel import (FreqModel, RooflineTerms, ScheduleResult, roofline,
                        simulate, task_time, transfer_time,
                        TPU_PEAK_FLOPS, TPU_HBM_BW, TPU_ICI_BW, TPU_DCN_BW)
from .scaleup import ScalePlan, graph_intensity, lm_pod_strategy, plan_scaleup
from .ilp import ILPError, Model, SolveStats


__all__ = [
    "Channel", "ResourceProfile", "Task", "TaskGraph", "linear_graph",
    "Bus", "Cluster", "DaisyChain", "DeviceSpec", "Hypercube", "Mesh2D",
    "Protocol", "Ring", "Star", "Topology", "lam",
    "ALVEO_U55C", "TPU_V5E", "ETHERNET_100G", "PCIE_GEN3X16",
    "INTER_NODE_10G", "TPU_ICI", "TPU_DCN",
    "fpga_ring_cluster", "tpu_pod_cluster",
    "Partition",
    "Floorplan", "SlotGrid", "U55C_GRID", "TPU_POD_GRID",
    "PipelineReport", "verify_balanced",
    "FreqModel", "RooflineTerms", "ScheduleResult", "roofline", "simulate",
    "task_time", "transfer_time",
    "TPU_PEAK_FLOPS", "TPU_HBM_BW", "TPU_ICI_BW", "TPU_DCN_BW",
    "ScalePlan", "graph_intensity", "lm_pod_strategy", "plan_scaleup",
    "ILPError", "Model", "SolveStats",
]
