"""One run of one cell: set-up, the measured window, and the check of what
the timed path produced against the plain reference.

Everything that belongs to a cell is found by name: the cell's entry in
``BENCHMARK.json``, its configuration file, the module of the file's
architecture under ``arch/``, its traffic mix under ``traffic/``, its
limits under ``limits/`` and each per-layer metric's reader under
``metrics/``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import statistics
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import arch as architecture  # noqa: E402
from chipbench import flops, peaks  # noqa: E402
from chipbench.reference import params as P  # noqa: E402
from chipbench.reference import train as R  # noqa: E402
from chipbench.traffic import TokenStream  # noqa: E402

# the system under test
from repro.colocation.stepper import ColocatedJob, TemporalStepper  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.optim.adamw import OptimizerConfig  # noqa: E402
from repro.train.steps import make_train_bundle  # noqa: E402

FIRST_STEPS = 3  # steps compared with the reference, taken in set-up
SOLO_STEPS = 3  # solo steps per job behind executor.weighted_speedup
NEVER = 10**12  # steps per epoch when the mix has no epoch boundary

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number of
    backend compiles, from its own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


# ---------------------------------------------------------------------------
# What a cell is, from its files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class JobSpec:
    name: str
    cfg: dict
    batch: int
    seq: int
    index: int

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    jobs: List[JobSpec]
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _read(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    files = {c["name"]: root / c["file"] for c in spec["configs"]}
    traffic = _read(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    names = [j["config"] for j in traffic["jobs"]]
    if names[0] != w["config"] or len(set(names)) != len(names):
        raise ValueError(f"{name}: the mix's jobs {names} must start with {w['config']!r}, each config once")
    jobs = [
        JobSpec(name=j["config"], cfg=_read(files[j["config"]]), batch=j["batch"], seq=j["seq"], index=i)
        for i, j in enumerate(traffic["jobs"])
    ]
    limits_file = root / "chipbench" / "limits" / f"{name}.json"
    return Cell(
        name=name,
        chips=w["chips"],
        jobs=jobs,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        limits=_read(limits_file) if limits_file.exists() else {},
    )


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# The program's side
# ---------------------------------------------------------------------------


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file, checked
    against the file's sizes by the file's architecture."""
    arch = get_config(cfg["program"]["registry"])
    changes = {}
    for k, v in cfg["program"]["replace"].items():
        cur = getattr(arch, k)
        changes[k] = dataclasses.replace(cur, **v) if isinstance(v, dict) else v
    arch = dataclasses.replace(arch, **changes)
    architecture.of(cfg).check(cfg, arch)
    return arch


def make_bundle(cfg: dict, mesh):
    """The program's train step for ``cfg``, with the file's optimizer."""
    t = cfg["train"]
    if t["optimizer"] != "adamw" or t["decay_rank_at_least"] != 2:
        raise ValueError(f"{cfg['name']}: the program's AdamW decays arrays of rank >= 2 only")
    lr = t["lr"]
    bundle = make_train_bundle(
        program_config(cfg),
        mesh,
        opt_cfg=OptimizerConfig(
            name="adamw", b1=t["b1"], b2=t["b2"], eps=t["eps"], weight_decay=t["weight_decay"]
        ),
        lr_schedule=lambda step: jnp.asarray(lr, jnp.float32),
        grad_clip=t["grad_clip"],
    )
    have = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), bundle.abstract_params)
    want = jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), P.abstract(cfg))
    if have != want:
        raise ValueError(f"{cfg['name']}: the program's parameters are not laid out as the reference's")
    return bundle


def make_mesh(traffic: dict, devices):
    shape = traffic.get("mesh")
    if shape is None:
        if len(devices) != 1:
            raise ValueError("a mix without a mesh runs on one chip")
        return None
    mesh = make_host_mesh(devices)
    if dict(mesh.shape) != shape:
        raise ValueError(f"mesh {dict(mesh.shape)} over {len(devices)} devices, the mix asks {shape}")
    return mesh


def stream(spec: JobSpec, traffic: dict, seed: int) -> TokenStream:
    tok = traffic["tokens"]
    return TokenStream(
        vocab_size=spec.cfg["vocab_size"],
        seq_len=spec.seq,
        batch=spec.batch,
        seed=(seed << 8) | spec.index,
        zipf_a=tok["zipf_a"],
        structure=tok["structure"],
    )


@dataclasses.dataclass
class Running:
    """One job of the cell: the program's job object and what set-up read."""

    spec: JobSpec
    job: Any  # ColocatedJob
    key: Any
    readings: dict = dataclasses.field(default_factory=dict)
    solo_step_s: Optional[float] = None


def _first_grad_norms(m, b1):
    """Each leaf's norm of the first gradient, from AdamW's first moment
    after one step (m = (1 - b1) g)."""
    return R.leaf_norms(jax.tree.map(lambda x: x / (1 - b1), m))


class Program:
    """The cell's jobs as the program runs them: one bundle per job, built
    once, and state made from a seed."""

    def __init__(self, cell: Cell, devices):
        if cell.traffic["order"] != "round_robin":
            raise ValueError(f"unknown order {cell.traffic['order']!r}")
        if cell.traffic.get("checkpoint") is not None:
            raise ValueError("checkpoints in the window are not supported yet")
        self.cell = cell
        self.devices = devices
        self.mesh = make_mesh(cell.traffic, devices)
        self.bundles = [make_bundle(s.cfg, self.mesh) for s in cell.jobs]
        self.make_state = [self._state_fn(s.cfg, b) for s, b in zip(cell.jobs, self.bundles)]
        self.grad_norms = [
            jax.jit(functools.partial(_first_grad_norms, b1=s.cfg["train"]["b1"])) for s in cell.jobs
        ]
        self.change_norms = [jax.jit(functools.partial(R.change_norms, s.cfg)) for s in cell.jobs]

    def _state_fn(self, cfg, bundle):
        def make(key):
            params = P.init(cfg, key)
            return params, bundle.optimizer.init(params)

        out = None if self.mesh is None else (bundle.param_shardings, bundle.opt_shardings)
        return jax.jit(make, out_shardings=out)

    def start(self, seed: int) -> List[Running]:
        """Fresh weights and optimizer state for every job, from ``seed``."""
        spe = self.cell.traffic.get("steps_per_epoch") or NEVER
        out = []
        for spec, bundle in zip(self.cell.jobs, self.bundles):
            key = P.seed_key(seed, spec.index)
            params, opt = self.make_state[spec.index](key)
            job = ColocatedJob(
                name=spec.name,
                bundle=bundle,
                pipeline=stream(spec, self.cell.traffic, seed),
                steps_per_epoch=spe,
                target_epochs=NEVER,
                params=params,
                opt_state=opt,
            )
            out.append(Running(spec, job, key))
        return out

    def first_steps(self, stepper, running: List[Running]) -> None:
        """The first ``FIRST_STEPS`` rounds through the window's own call,
        reading what the reference is compared on: each step's loss, the
        first gradient as the optimizer holds it, the change after the steps."""
        stepper.step_round()
        for r in running:
            norms = self.grad_norms[r.spec.index](r.job.opt_state.m)
            r.readings["grad_norms"] = {k: float(v) for k, v in norms.items()}
        for _ in range(FIRST_STEPS - 1):
            stepper.step_round()
        for r in running:
            norms = self.change_norms[r.spec.index](r.key, r.job.params)
            r.readings["change_norms"] = {k: float(v) for k, v in norms.items()}
            r.readings["losses"] = list(r.job.losses[:FIRST_STEPS])


def state_bytes(running: List[Running], devices) -> List[int]:
    """Bytes of every job's parameters and optimizer state on each device."""
    per = [0] * len(devices)
    for r in running:
        for leaf in jax.tree.leaves((r.job.params, r.job.opt_state)):
            for shard in leaf.addressable_shards:
                per[devices.index(shard.device)] += shard.data.nbytes
    return per


def temp_bytes(r: Running) -> int:
    """Temporaries of the job's compiled step program on one device."""
    tokens, labels = r.job.pipeline.batch_at(0)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    compiled = r.job.bundle.step_fn.lower(r.job.params, r.job.opt_state, batch).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def free(running: List[Running]) -> None:
    for r in running:
        r.job.params = r.job.opt_state = None
    gc.collect()


def reference_batches(spec: JobSpec, traffic: dict, seed: int, half: bool = False):
    """The batches of the first steps, from the benchmark's generator.
    ``half`` leaves out half of each batch (rows, or the later half of the
    positions of a single row): one of the faults the check must catch."""
    out = []
    for i in range(FIRST_STEPS):
        t, l = stream(spec, traffic, seed).batch_at(i)
        if half:
            b, s = t.shape
            t, l = (t[: b // 2], l[: b // 2]) if b > 1 else (t[:, : s // 2], l[:, : s // 2])
        out.append((t, l))
    return out


def compare(cell: Cell, running: List[Running], refs: Dict[str, dict]) -> Dict[str, dict]:
    """Each job's gaps beside their limits.  A number that the cell's limits
    file leaves out of a job is not compared; a job it leaves out fails."""
    out = {}
    for r in running:
        gaps = R.gaps(r.readings, refs[r.spec.name])
        for k, limit in (cell.limits.get(r.spec.name) or {"loss": None}).items():
            out[f"{r.spec.name}.{k}"] = {"value": gaps[k], "limit": limit}
    return out


def is_correct(compared: Dict[str, dict]) -> bool:
    return bool(compared) and all(
        c["limit"] is not None and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values()
    )


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    devices,
    t_start: float,
    trace_dir: Optional[Path] = None,
    log=print,
) -> dict:
    """Set up, measure for ``seconds``, check, and return the result line."""
    clock = CompileClock()
    kind = devices[0].device_kind
    peak = peaks.peak(kind)["bf16_flops_per_s"] if devices[0].platform == "tpu" else None
    program = Program(cell, devices)
    running = program.start(seed)
    stepper = TemporalStepper([r.job for r in running])
    program.first_steps(stepper, running)
    resident = state_bytes(running, devices)
    temps = [temp_bytes(r) for r in running]
    if trace and len(running) > 1:
        for r in running:
            alone = TemporalStepper([r.job])
            for _ in range(SOLO_STEPS):
                alone.step_round()
            r.solo_step_s = statistics.median(r.job.step_times[-SOLO_STEPS:])
    jax.block_until_ready([(r.job.params, r.job.opt_state) for r in running])
    log(f"set-up: compile {clock.seconds:.1f} s in {clock.compiles} compiles")

    before = [len(r.job.step_times) for r in running]
    compiles_before = clock.compiles
    if trace:
        trace_dir.mkdir(parents=True, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation("chipbench.round"):
                stepper.step_round()
    t1 = time.perf_counter()
    window_s = t1 - t0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        from chipbench import trace as tr

        reduced = tr.reduce_dir(str(trace_dir), [d.id for d in devices])
    in_window = clock.compiles - compiles_before
    if in_window:
        log(f"warning: {in_window} compile(s) inside the window")

    stats = [d.memory_stats() or {} for d in devices]
    counted = max(resident) + max(temps)
    jobs = []
    for r, n0 in zip(running, before):
        times = r.job.step_times[n0:]
        jobs.append(
            {
                "name": r.spec.name,
                "steps": len(times),
                "step_s": times,
                "tokens_per_step": r.spec.tokens_per_step,
                "flops_per_step": flops.train_step_flops(r.spec.cfg, r.spec.batch, r.spec.seq),
                "solo_step_s": r.solo_step_s,
            }
        )
    record = {
        "window_s": window_s,
        "chips": len(devices),
        "peak_flops_per_s": peak,
        "jobs": jobs,
        "trace": reduced,
    }
    del stepper
    free(running)

    refs = {}
    for r in running:
        batches = reference_batches(r.spec, cell.traffic, seed)
        refs[r.spec.name] = R.readings(r.spec.cfg, r.key, batches, "float32", devices)
    compared = compare(cell, running, refs)

    end_to_end = {
        "tokens_per_s": sum(j["steps"] * j["tokens_per_step"] for j in jobs) / window_s,
        "peak_hbm_bytes": counted,
        "setup_s": setup_s,
    }
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = metric_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end[m["name"]], "unit": m["unit"]}
    peak_in_use = max(s.get("peak_bytes_in_use", 0) for s in stats)
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": max(peak_in_use, counted),
    }
    result = {
        "correct": is_correct(compared),
        "attempted": sum(j["steps"] for j in jobs),
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": [list(g) for g in reduced["idle_gaps"]],
        }
    log(
        "detail: "
        + json.dumps(
            {
                "state_bytes": resident,
                "temp_bytes": temps,
                "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
                "compiles_in_window": in_window,
                "steps": {j["name"]: j["steps"] for j in jobs},
                "median_step_s": {j["name"]: statistics.median(j["step_s"]) for j in jobs if j["step_s"]},
                "solo_step_s": {j["name"]: j["solo_step_s"] for j in jobs},
                "losses": {r.spec.name: r.job.losses[:FIRST_STEPS] for r in running},
                "reference_losses": {k: v["losses"] for k, v in refs.items()},
                "gaps": {r.spec.name: R.gaps(r.readings, refs[r.spec.name]) for r in running},
                "idle_by_span": reduced and reduced["idle_by_span"],
            }
        )
    )
    result["compared"] = compared
    return result
