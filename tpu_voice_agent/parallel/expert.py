"""Expert parallelism: top-k routed MoE FFN over an ``ep`` mesh axis.

The reference has no MoE (SURVEY.md §2 audit table: EP "absent … n/a unless
MoE checkpoint added"); this module completes the parallelism inventory so
an MoE planner checkpoint (e.g. a Mixtral-style decoder) drops in without
new collective machinery.

Design (TPU-first, exact — no token dropping below capacity):

- routing and dispatch are dense einsums over a one-hot (token, expert,
  slot) tensor — XLA turns these into MXU matmuls; no scatter/gather with
  data-dependent shapes, which would defeat jit
- ``moe_ffn`` is the single-device reference; ``moe_ffn_ep`` shard_maps the
  stacked expert weights over ``ep``: router logits are computed everywhere
  (router weights replicate), each device builds dispatch/combine tensors
  for its local expert shard only, runs its experts' SwiGLU, and a single
  ``psum`` over ``ep`` completes the combine. Activations replicate across
  ``ep`` — the right trade for the moderate token counts of an interactive
  planner; an all_to_all token-exchange layout (cheaper at very large T)
  composes from the same dispatch tensors if a config needs it.
- capacity C bounds each expert's slot count; overflow tokens lose that
  expert's contribution (standard Switch/GShard semantics) and the combine
  weights renormalize over the surviving experts.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    dim: int
    ffn_dim: int
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25

    def capacity(self, n_tokens: int) -> int:
        from ..models.moe import moe_capacity

        return moe_capacity(n_tokens, self.n_experts, self.top_k, self.capacity_factor)


def ep_mesh(ep: int, devices: list | None = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if ep > len(devices):
        raise ValueError(f"ep={ep} needs {ep} devices, have {len(devices)}")
    return Mesh(np.array(devices[:ep]), ("ep",))


def init_moe_params(cfg: MoEConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Router replicates; expert weights stack on a leading E axis (sharded
    over ep by the caller via ``moe_param_shardings``)."""
    kr, kg, ku, kd = jax.random.split(key, 4)
    d, f, E = cfg.dim, cfg.ffn_dim, cfg.n_experts

    def w(key, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    return {
        "router": w(kr, d, E),
        "w_gate": w(kg, E, d, f),
        "w_up": w(ku, E, d, f),
        "w_down": w(kd, E, f, d),
    }


def moe_param_shardings(mesh: Mesh) -> dict:
    from jax.sharding import NamedSharding

    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    return {
        "router": ns(None, None),
        "w_gate": ns("ep", None, None),
        "w_up": ns("ep", None, None),
        "w_down": ns("ep", None, None),
    }


def _route(router_w: jax.Array, x: jax.Array, cfg: MoEConfig, n_tokens: int):
    """Shared routing math -> (dispatch (T,E,C) one-hot, combine (T,E,C)).
    Delegates to models.moe.route_topk — ONE copy of the routing math for
    the standalone EP layer and the served MoE decoder (models.llama)."""
    from ..models.moe import route_topk

    return route_topk(router_w, x, cfg.n_experts, cfg.top_k, cfg.capacity(n_tokens))


def _expert_ffn(p: dict, xe: jax.Array) -> jax.Array:
    """xe (E, C, d) -> (E, C, d), per-expert SwiGLU in bf16/f32-accum."""
    gate = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.einsum("ecd,edf->ecf", xe, p["w_up"], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(xe.dtype)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"],
                      preferred_element_type=jnp.float32).astype(xe.dtype)


# analyze: ok[jit-sentinel] -- MoE FFN traced inline by llama.forward's watched layer stack; jitted standalone only for unit tests
@partial(jax.jit, static_argnames=("cfg",))
def moe_ffn(params: dict, cfg: MoEConfig, x: jax.Array) -> jax.Array:
    """Single-device reference. x (T, d) -> (T, d)."""
    T = x.shape[0]
    dispatch, combine = _route(params["router"], x, cfg, T)
    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)  # (E, C, d)
    ye = _expert_ffn(params, xe)
    return jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)


def moe_ffn_ep(params: dict, cfg: MoEConfig, x: jax.Array, mesh: Mesh) -> jax.Array:
    """EP execution: experts sharded over ``ep``, activations replicated,
    one psum completes the combine. Numerically matches ``moe_ffn``."""
    if cfg.n_experts % mesh.shape["ep"]:
        raise ValueError(f"n_experts {cfg.n_experts} must divide ep={mesh.shape['ep']}")

    def local(router_w, w_gate, w_up, w_down, x):
        ep = jax.lax.axis_index("ep")
        n_local = w_gate.shape[0]
        T = x.shape[0]
        dispatch, combine = _route(router_w, x, cfg, T)  # full (T, E, C)
        # slice this device's expert block out of the dense routing tensors
        e0 = ep * n_local
        d_loc = jax.lax.dynamic_slice_in_dim(dispatch, e0, n_local, axis=1)
        c_loc = jax.lax.dynamic_slice_in_dim(combine, e0, n_local, axis=1)
        xe = jnp.einsum("tec,td->ecd", d_loc.astype(x.dtype), x)
        ye = _expert_ffn({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, xe)
        out = jnp.einsum("tec,ecd->td", c_loc.astype(x.dtype), ye)
        return jax.lax.psum(out, "ep")

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None), P("ep", None, None), P("ep", None, None),
                  P("ep", None, None), P(None, None)),
        out_specs=P(None, None),
    )
    return fn(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)
