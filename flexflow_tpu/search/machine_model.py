"""TPU machine/topology model for the cost simulator.

Rebuild of the reference's MachineModel hierarchy (include/flexflow/
simulator.h:212-606, src/runtime/machine_model.cc, network.cc): the simulator
needs per-device compute rates and link bandwidths/latencies to cost candidate
strategies. The reference models membus/UPI/NIC/PCIe/NVLink
(machine_config_example:1-30); here the hierarchy is TPU-native:

* per-chip: peak FLOP/s (bf16 and f32), HBM bandwidth and capacity
* ICI: torus links within a slice (per-link GB/s, hop latency)
* DCN: bisection bandwidth across slices

Version selection mirrors the reference (graph.cc:1908-1922):
``machine_model_version == 0`` -> SimpleTPUMachineModel from generation
defaults; ``1`` -> parsed from ``--machine-model-file``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


# generation defaults: (peak bf16 FLOP/s, HBM GB/s, HBM GiB,
#                       ICI GB/s per link per direction, ici links/chip)
# — the published per-chip figures (Google Cloud TPU documentation; v5e:
# 197 TFLOP/s bf16, 819 GB/s, 16 GB). The ONE peak table: telemetry MFU,
# bench MFU and the cost model all read it.
TPU_GENERATIONS = {
    "v4": (275e12, 1228e9, 32, 50e9, 6),
    "v5e": (197e12, 819e9, 16, 50e9, 4),
    "v5p": (459e12, 2765e9, 95, 100e9, 6),
    "v6e": (918e12, 1640e9, 32, 100e9, 4),
}


def detect_generation(device_kind: str):
    """Normalize a jax ``device_kind`` string to a TPU_GENERATIONS key
    ('TPU v5 lite' -> 'v5e'), or None when unrecognized. The ONE place the
    kind-string matching lives — TPUMachineModel.detect, the telemetry peak
    (obs/telemetry.detect_peak_flops) and the flash crossover table
    (ops/attention.FLASH_TUNING) all key off it through
    ``local_tpu_generation``."""
    kind = device_kind.lower().replace(" ", "").replace("lite", "e")
    for gen in TPU_GENERATIONS:
        if gen in kind:
            return gen
    return None


def local_tpu_generation() -> Optional[str]:
    """TPU_GENERATIONS key of this process's first device, or None off-TPU
    (the CPU test mesh). A TPU whose ``device_kind`` is outside the table
    raises — no assumed generation, no assumed peak."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    gen = detect_generation(dev.device_kind)
    if gen is None:
        raise RuntimeError(
            f"unknown TPU device_kind {dev.device_kind!r}: not in "
            f"machine_model.TPU_GENERATIONS ({sorted(TPU_GENERATIONS)}); "
            f"add its row (peak FLOP/s, HBM, ICI) with a source")
    return gen


@dataclasses.dataclass
class TPUMachineModel:
    """Analog of MachineModel v0/v1 with TPU parameters."""

    num_chips: int = 1
    # hosts/slices connected by DCN; chips within a host share an ICI torus.
    # Mirrors the reference's inter-node vs intra-node split
    # (EnhancedMachineModel, simulator.h:212-606; machine_config_example's
    # NIC vs NVLink rows).
    num_hosts: int = 1
    # multi-pod topologies (docs/multipod.md): a POD is one ICI domain —
    # the DCN island the hierarchical search's ICI level solves within.
    # 0 = pods follow ``num_hosts`` (every DCN island is one pod, the
    # single-level machines that predate the pod axis); >= 2 records an
    # explicit pod count, which in this cost model IS the DCN split
    # (``num_hosts`` is kept equal — one DCN level, priced by the
    # hier_* closed forms below).
    num_pods: int = 0
    generation: str = "v5e"
    peak_flops: float = 197e12  # bf16
    peak_flops_f32: float = 98.5e12
    hbm_bandwidth: float = 819e9  # bytes/s
    hbm_capacity: int = 16 * 1024 ** 3  # bytes
    ici_bandwidth: float = 50e9  # bytes/s per link per direction
    ici_links_per_chip: int = 4
    ici_latency: float = 1e-6  # seconds per hop
    torus: Tuple[int, ...] = (1,)  # ICI torus dims, prod == chips per slice
    dcn_bandwidth: float = 25e9  # bytes/s per host across slices
    dcn_latency: float = 10e-6
    # fraction of peak realistically achieved by large matmuls
    matmul_efficiency: float = 0.6
    # fraction of HBM bandwidth achieved by fused elementwise ops
    hbm_efficiency: float = 0.8
    # fraction achieved by the 7-stream optimizer update (4 concurrent
    # reads + 3 writes): measured on v5e — a fused Adam moves 705 MB in
    # 1.63 ms (~435 GB/s) and the BERT-Large profile shows ~495 GB/s, far
    # below the single-stream 0.8. Overridable per machine via machine.cfg.
    update_hbm_efficiency: float = 0.55

    @staticmethod
    def from_generation(gen: str, num_chips: int = 1,
                        torus: Optional[Tuple[int, ...]] = None,
                        num_hosts: int = 1) -> "TPUMachineModel":
        peak, hbm_bw, hbm_gib, ici_bw, links = TPU_GENERATIONS.get(
            gen, TPU_GENERATIONS["v5e"])
        if torus is None:
            torus = _default_torus(num_chips // max(num_hosts, 1))
        return TPUMachineModel(
            num_chips=num_chips, num_hosts=num_hosts, generation=gen,
            peak_flops=peak,
            peak_flops_f32=peak / 2, hbm_bandwidth=hbm_bw,
            hbm_capacity=hbm_gib * 1024 ** 3, ici_bandwidth=ici_bw,
            ici_links_per_chip=links, torus=torus)

    @staticmethod
    def from_file(path: str, num_chips: int = 1) -> "TPUMachineModel":
        """v1: key = value lines (analog of machine_config_example).

        Multi-pod fields (docs/multipod.md): ``num_pods`` declares the
        pod count (each pod one ICI domain; pods connected by DCN) and
        ``dcn_bisection_gbps`` the per-pod DCN bandwidth in GB/s —
        both validated at parse time with errors naming the bad field,
        so a typo'd topology file fails before a 4096-chip search prices
        a machine that doesn't exist."""
        kv: Dict[str, str] = {}
        with open(path) as f:
            for line in f:
                line = line.split("#")[0].strip()
                if "=" in line:
                    k, v = line.split("=", 1)
                    kv[k.strip()] = v.strip()

        def _bad(field: str, why: str):
            return ValueError(
                f"machine model file {path}: field {field!r} = "
                f"{kv[field]!r} is invalid: {why}")

        num_pods = 0
        if "num_pods" in kv:
            try:
                num_pods = int(kv["num_pods"])
            except ValueError:
                raise _bad("num_pods", "expected an integer pod count")
            if num_pods < 1:
                raise _bad("num_pods", "the machine needs >= 1 pod")
            if num_chips % num_pods:
                raise _bad(
                    "num_pods",
                    f"must divide num_chips={num_chips} — a pod is a "
                    "whole ICI domain, chips cannot straddle pods")
        # num_hosts feeds the default-torus computation (invariant:
        # prod(torus) == chips per slice), so parse it BEFORE construction
        num_hosts = int(kv.get("num_hosts", 1))
        if num_pods:
            if "num_hosts" in kv and num_hosts != num_pods:
                raise _bad(
                    "num_pods",
                    f"conflicts with num_hosts={num_hosts}: this cost "
                    "model has ONE DCN level, so pods ARE the DCN "
                    "islands — drop one field or make them equal")
            num_hosts = num_pods
        m = TPUMachineModel.from_generation(kv.get("generation", "v5e"),
                                            num_chips, num_hosts=num_hosts)
        m.num_pods = num_pods
        if "dcn_bisection_gbps" in kv:
            try:
                gbps = float(kv["dcn_bisection_gbps"])
            except ValueError:
                raise _bad("dcn_bisection_gbps",
                           "expected a number (GB/s per pod across DCN)")
            if gbps <= 0:
                raise _bad("dcn_bisection_gbps",
                           "DCN bandwidth must be > 0 GB/s")
            m.dcn_bandwidth = gbps * 1e9
        for field in ("peak_flops", "hbm_bandwidth", "ici_bandwidth",
                      "dcn_bandwidth", "ici_latency", "dcn_latency",
                      "matmul_efficiency", "hbm_efficiency",
                      "update_hbm_efficiency"):
            if field in kv:
                setattr(m, field, float(kv[field]))
        if "hbm_capacity" in kv:
            m.hbm_capacity = int(float(kv["hbm_capacity"]))
        if "torus" in kv:
            m.torus = tuple(int(x) for x in kv["torus"].split("x"))
        return m

    @staticmethod
    def multipod(generation: str, num_pods: int, chips_per_pod: int,
                 dcn_gbps: float = 0.0) -> "TPUMachineModel":
        """A simulated multi-pod machine: ``num_pods`` ICI domains of
        ``chips_per_pod`` chips each, connected by DCN (cost model only —
        the hierarchical search's regression topologies run on CPU)."""
        if num_pods < 1:
            raise ValueError(f"multipod: num_pods must be >= 1, got "
                             f"{num_pods}")
        if chips_per_pod < 1:
            raise ValueError(f"multipod: chips_per_pod must be >= 1, got "
                             f"{chips_per_pod}")
        m = TPUMachineModel.from_generation(
            generation, num_pods * chips_per_pod, num_hosts=num_pods)
        m.num_pods = num_pods
        if dcn_gbps:
            if dcn_gbps <= 0:
                raise ValueError(
                    f"multipod: dcn_gbps must be > 0, got {dcn_gbps}")
            m.dcn_bandwidth = dcn_gbps * 1e9
        return m

    def apply_pod_overrides(self, num_pods: int = 0,
                            dcn_gbps: float = 0.0) -> "TPUMachineModel":
        """Apply the ``--pods`` / ``--dcn-gbps`` CLI overrides onto a
        constructed machine (unity_search's machine-from-config path)."""
        if num_pods:
            if num_pods < 1:
                raise ValueError(
                    f"--pods must be >= 1, got {num_pods}")
            if self.num_chips % num_pods:
                raise ValueError(
                    f"--pods {num_pods} does not divide the machine's "
                    f"{self.num_chips} chips — a pod is a whole ICI "
                    "domain, chips cannot straddle pods")
            self.set_num_hosts(num_pods)
            self.num_pods = num_pods
        if dcn_gbps:
            if dcn_gbps <= 0:
                raise ValueError(
                    f"--dcn-gbps must be > 0, got {dcn_gbps}")
            self.dcn_bandwidth = dcn_gbps * 1e9
        return self

    def set_num_hosts(self, num_hosts: int) -> "TPUMachineModel":
        """Re-split the machine into ``num_hosts`` DCN-connected slices,
        recomputing the per-slice torus (mutating ``num_hosts`` directly
        would leave ``torus`` spanning the whole machine)."""
        self.num_hosts = max(num_hosts, 1)
        self.torus = _default_torus(self.chips_per_host)
        return self

    @staticmethod
    def detect(num_chips: Optional[int] = None,
               num_hosts: Optional[int] = None) -> "TPUMachineModel":
        """Build from the visible JAX devices. The CPU test mesh gets v5e
        params so search decisions are deterministic on CI; a TPU whose
        device_kind is not in TPU_GENERATIONS raises."""
        import jax

        devs = jax.devices()
        n = num_chips or len(devs)
        # multi-host runs: each process owns one slice's worth of chips, so
        # the DCN factor is the process count (hosts == slices here)
        hosts = num_hosts or \
            (jax.process_count() if n == len(devs) else 1)
        if n % max(hosts, 1) != 0:
            # silent reset would hand an explicit multi-host caller a
            # single-host cost model with no signal (ADVICE r4)
            import warnings

            warnings.warn(
                f"TPUMachineModel.detect: num_hosts={hosts} does not divide "
                f"num_chips={n}; falling back to a single-host model",
                stacklevel=2)
            hosts = 1
        return TPUMachineModel.from_generation(
            local_tpu_generation() or "v5e", n, num_hosts=hosts)

    @property
    def chips_per_host(self) -> int:
        return max(self.num_chips // max(self.num_hosts, 1), 1)

    @property
    def pods(self) -> int:
        """Pod count of the machine: the explicit ``num_pods`` when set,
        else the host count (single-level machines: every DCN island is
        one pod)."""
        return max(self.num_pods or self.num_hosts, 1)

    @property
    def chips_per_pod(self) -> int:
        return max(self.num_chips // self.pods, 1)

    # ---- communication cost primitives (α-β model over the torus) -----------
    # ``medium``: "ici" (within a slice) or "dcn" (across hosts). DCN is a
    # per-HOST NIC shared by every chip of the slice — ``nic_sharers`` is the
    # number of chips on one host participating in concurrent distinct
    # collective groups, dividing the NIC bandwidth between them (reference:
    # EnhancedMachineModel's shared NIC channel, simulator.h:311-364).
    def _link(self, medium: str, nic_sharers: int, links: int
              ) -> Tuple[float, float]:
        if medium == "dcn":
            return (self.dcn_bandwidth / max(nic_sharers, 1),
                    self.dcn_latency)
        return (self.ici_bandwidth * links, self.ici_latency)

    def _ici_ring(self, num_participants: int) -> Tuple[int, int]:
        """(usable links, per-round latency hops) for a ring collective over
        ``num_participants`` chips laid out contiguously on the ICI torus.

        Torus-aware analog of the reference's topology-driven routing
        (NetworkedMachineModel topology generators + routing strategies,
        include/flexflow/simulator.h:383-606, src/runtime/network.cc): a
        group spanning k torus axes runs k concurrent bidirectional rings
        (2k links per chip), and the ring phases are per-axis, so the hop
        count is the sum of axis extents, not the flat group size."""
        rem = max(num_participants, 1)
        axes = 0
        hops = 0
        for d in self.torus:
            if d <= 1:
                continue  # degenerate axis: no ring exists along it
            if rem <= 1 or rem % d:
                break
            axes += 1
            hops += d - 1
            rem //= d
        if rem > 1:
            # leftover that doesn't fill an axis rides a single embedded
            # ring — extra hops, no extra concurrent rings
            hops += rem - 1
        links = min(2 * max(axes, 1), self.ici_links_per_chip)
        return links, max(hops, 1)

    def allreduce_time(self, bytes_per_chip: int, num_participants: int,
                       medium: str = "ici", nic_sharers: int = 1) -> float:
        """Ring all-reduce: 2*(n-1)/n * bytes over the per-chip link
        bandwidth. On ICI the torus shape decides how many bidirectional
        rings run concurrently (one per spanned axis — 2 links each)."""
        if num_participants <= 1 or bytes_per_chip == 0:
            return 0.0
        if medium == "ici":
            links, hops = self._ici_ring(num_participants)
            eff_bw, lat = self._link(medium, nic_sharers, links)
            n = num_participants
            return (lat * 2 * hops
                    + 2 * (n - 1) / n * bytes_per_chip / eff_bw)
        eff_bw, lat = self._link(medium, nic_sharers, 2)
        steps = 2 * (num_participants - 1)
        return (lat * steps
                + steps / num_participants * bytes_per_chip / eff_bw)

    def allgather_time(self, bytes_per_chip: int, num_participants: int,
                       medium: str = "ici", nic_sharers: int = 1) -> float:
        if num_participants <= 1 or bytes_per_chip == 0:
            return 0.0
        if medium == "ici":
            links, hops = self._ici_ring(num_participants)
            eff_bw, lat = self._link(medium, nic_sharers, links)
            n = num_participants
            return (lat * hops
                    + (n - 1) * bytes_per_chip / eff_bw)
        eff_bw, lat = self._link(medium, nic_sharers, 2)
        steps = num_participants - 1
        return (lat * steps
                + steps * bytes_per_chip / eff_bw)

    def alltoall_time(self, bytes_per_chip: int, num_participants: int,
                      medium: str = "ici", nic_sharers: int = 1) -> float:
        if num_participants <= 1 or bytes_per_chip == 0:
            return 0.0
        # each chip exchanges (n-1)/n of its data over its links
        eff_bw, lat = self._link(medium, nic_sharers,
                                 self.ici_links_per_chip)
        return (lat * (num_participants - 1)
                + bytes_per_chip * (num_participants - 1)
                / num_participants / eff_bw)

    def p2p_time(self, num_bytes: int, medium: str = "ici") -> float:
        if medium == "dcn":
            return self.dcn_latency + num_bytes / self.dcn_bandwidth
        return self.ici_latency + num_bytes / self.ici_bandwidth

    # ---- hierarchical (ICI within a slice, DCN across) ----------------------
    # The standard multi-slice algorithm: reduce within the slice first so
    # only 1/ici_n of the data crosses DCN, then the cross-slice phase, then
    # the local broadcast (the reduce-scatter + allgather pair costs the same
    # as one local allreduce in ring terms).
    def hier_allreduce_time(self, bytes_per_chip: int, ici_n: int,
                            dcn_n: int, nic_sharers: int = 1) -> float:
        if dcn_n <= 1:
            return self.allreduce_time(bytes_per_chip, ici_n)
        t = self.allreduce_time(bytes_per_chip, ici_n)
        t += self.allreduce_time(bytes_per_chip // max(ici_n, 1), dcn_n,
                                 medium="dcn", nic_sharers=nic_sharers)
        return t

    def hier_allgather_time(self, bytes_per_chip: int, ici_n: int,
                            dcn_n: int, nic_sharers: int = 1) -> float:
        if dcn_n <= 1:
            return self.allgather_time(bytes_per_chip, ici_n)
        # gather across DCN first (small shards), then flood the slice
        t = self.allgather_time(bytes_per_chip, dcn_n, medium="dcn",
                                nic_sharers=nic_sharers)
        t += self.allgather_time(bytes_per_chip * dcn_n, ici_n)
        return t

    def hier_alltoall_time(self, bytes_per_chip: int, ici_n: int,
                           dcn_n: int, nic_sharers: int = 1) -> float:
        if dcn_n <= 1:
            return self.alltoall_time(bytes_per_chip, ici_n)
        # (dcn_n-1)/dcn_n of each chip's data crosses DCN; the rest rides ICI
        dcn_frac = (dcn_n - 1) / dcn_n
        t = self.alltoall_time(int(bytes_per_chip * dcn_frac) + 1, dcn_n,
                               medium="dcn", nic_sharers=nic_sharers)
        t += self.alltoall_time(bytes_per_chip // max(dcn_n, 1), ici_n)
        return t


def _default_torus(n: int) -> Tuple[int, ...]:
    # closest-to-square 2D torus
    import math

    a = int(math.isqrt(n))
    while a > 1 and n % a:
        a -= 1
    return (a, n // a) if a > 1 else (n,)
