"""Driver of the `moe_train` traffic kind: the program's expert-parallel
model (``zhpe_ompi_tpu.models.mla_moe``) trained through
``make_train_step_optax`` with AdamW over a ("dp", "tp") mesh of tp 1, its
experts spread over the dp ranks and exchanged by the dp communicator's
all-to-all.

It is the `lm_train` driver with another step builder and communicator:
the feed, the window, the traced steps, :func:`reference_readings` and
:func:`compare` are `lm_train`'s own.  After the window the run prints the
last step's routing counters (``moe_dropped_assignments``,
``moe_routed_rows``, ``moe_max_expert_rows``) to standard error.
"""

from __future__ import annotations

import os
import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import harness
import zhpe_ompi_tpu as zmpi
from zhpe_ompi_tpu.models import mla_moe
from zhpe_ompi_tpu.tools import mpit

lm = harness.load_module(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "drivers", "lm_train")

CHECK_STEPS = lm.CHECK_STEPS
reference_readings = lm.reference_readings
compare = lm.compare
COUNTERS = ("moe_dropped_assignments", "moe_routed_rows",
            "moe_max_expert_rows")


def model_config(cfg: dict, traffic: dict, levers: dict) -> mla_moe.Config:
    return mla_moe.Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        dense_ff=cfg["intermediate_size"],
        expert_ff=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts_published"],
        top_k=cfg["num_experts_per_tok"], n_shared=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"],
        n_layers=cfg["num_hidden_layers"],
        n_dense=cfg["first_k_dense_replace"], rope_theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"], capacity_factor=cfg["capacity_factor"],
        dtype=jnp.dtype(cfg["dtype"]), **levers)


def make_step(cell, mesh):
    """The system under test: ``(init_opt_state, step, param_specs)``."""
    if mesh.shape["tp"] != 1:
        raise ValueError("moe_train runs a tp 1 mesh")
    cfg = cell.config
    if cfg["n_routed_experts"] != cfg["experts_per_chip"] * mesh.shape["dp"]:
        raise ValueError("each dp rank holds experts_per_chip experts")
    dp_comm = zmpi.Communicator(mesh, "dp", name="perfbench_dp")
    return mla_moe.make_train_step(
        model_config(cfg, cell.traffic, cell.workload["program"]), mesh,
        dp_comm, optimizer=lm.make_optimizer(cell.workload["optimizer"]))


def counters() -> dict:
    pv = mpit.registered_pvars()
    return {k: pv[k].reader() for k in COUNTERS}


class Job(lm.Job):
    """`lm_train`'s job around this kind's step."""

    def __init__(self, cell, devs, seed: int):
        self.cell = cell
        self.devs = devs
        dp, tp = cell.workload["mesh"]["dp"], cell.workload["mesh"]["tp"]
        self.mesh = Mesh(np.asarray(devs).reshape(dp, tp), ("dp", "tp"))
        ref = harness.reference_module(cell)
        init_opt, self.step, specs = make_step(cell, self.mesh)
        self.shardings = {k: NamedSharding(self.mesh, s)
                          for k, s in specs.items()}
        self.init_opt = init_opt
        self.init = jax.jit(lambda k: ref.init_params(cell.config, k),
                            out_shardings=self.shardings)
        self.batch = lm.make_batch_fn(cell.traffic,
                                      NamedSharding(self.mesh, P("dp")))
        self.reset(seed)

    def free(self):
        """Frees the state and, with ``jax.clear_caches``, unloads the
        step's programs: a loaded TPU program keeps its scratch memory
        reserved, and the reference that follows needs it."""
        if self.params is not None:
            print(f"perfbench: {self.cell.name} last step {counters()}",
                  file=sys.stderr)
        super().free()
        jax.clear_caches()
