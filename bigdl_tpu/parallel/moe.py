"""Mixture-of-Experts with expert parallelism over an 'expert' mesh axis.

Beyond-parity (the reference scales only by data parallelism): top-1
(Switch) or top-2 (GShard) routing with per-group capacity, experts
sharded one-or-more-per-device, token exchange via `lax.all_to_all` — the
ICI-native MoE dispatch (Mesh-TensorFlow / Switch-Transformer algorithm).
The dense single-device `apply` is the numerical reference the
expert-parallel path must match on undropped tokens.

Training support: `apply_with_aux` returns the Switch load-balancing
auxiliary loss (n_experts * sum_e f_e * P_e — minimized at uniform
routing) plus routing statistics (per-expert load fraction, router
entropy), so a training loop can add `aux_weight * aux_loss` to its
objective and monitor balance; `tests/test_pipeline_moe.py` shows the
loss actually balancing a skewed router.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.nn.module import ApplyContext, Module


class MoE(Module):
    """Switch/GShard-style FFN MoE: router -> top-k experts -> gated sum.

    params: router [d, E] + stacked expert FFNs (w1 [E, d, h], b1 [E, h],
    w2 [E, h, d], b2 [E, d]). `capacity_factor` bounds tokens per expert;
    overflow tokens pass through with a zero expert contribution
    (standard Switch behavior). `top_k` = 1 (Switch) or 2 (GShard; gates
    renormalized over the chosen pair).

    Example (expert-parallel over 4 devices matches the dense reference):
        >>> import jax, jax.numpy as jnp, numpy as np
        >>> from jax.sharding import Mesh
        >>> from bigdl_tpu.parallel.moe import MoE
        >>> moe = MoE(d_model=8, d_hidden=16, n_experts=4,
        ...           capacity_factor=4.0)  # high cap: no dropped tokens
        >>> params = moe.init(jax.random.PRNGKey(0))
        >>> x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        >>> y, aux = moe.apply_with_aux(params, x)
        >>> y.shape, aux["expert_fraction"].shape
        ((16, 8), (4,))
        >>> mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))
        >>> y_ep = moe.expert_parallel_apply(mesh, params, x)
        >>> bool(jnp.allclose(y_ep, y, atol=1e-5))
        True
    """

    def __init__(self, d_model: int, d_hidden: int, n_experts: int,
                 capacity_factor: float = 1.25, top_k: int = 1, name=None):
        super().__init__(name)
        if top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2, got {top_k}")
        if top_k > n_experts:
            raise ValueError(
                f"top_k={top_k} exceeds n_experts={n_experts}")
        self.d, self.h, self.E = d_model, d_hidden, n_experts
        self.capacity_factor = capacity_factor
        self.top_k = top_k

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        s_in = 1.0 / math.sqrt(self.d)
        s_h = 1.0 / math.sqrt(self.h)
        return {
            "router": jax.random.uniform(k1, (self.d, self.E),
                                         minval=-s_in, maxval=s_in),
            "w1": jax.random.uniform(k2, (self.E, self.d, self.h),
                                     minval=-s_in, maxval=s_in),
            "b1": jnp.zeros((self.E, self.h)),
            "w2": jax.random.uniform(k3, (self.E, self.h, self.d),
                                     minval=-s_h, maxval=s_h),
            "b2": jnp.zeros((self.E, self.d)),
        }

    def _gates(self, params, x2d):
        """Top-k routing: experts [T, k], gates [T, k] (sum to the top-k
        mass, renormalized for k>1), probs [T, E] for the aux loss."""
        logits = x2d @ params["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, experts = lax.top_k(probs, self.top_k)   # [T, k]
        if self.top_k > 1:
            gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1,
                                            keepdims=True)
        return experts, gate_vals, probs

    def _expert_ffn(self, params, e, tokens):
        h = jnp.maximum(tokens @ params["w1"][e] + params["b1"][e], 0.0)
        return h @ params["w2"][e] + params["b2"][e]

    @staticmethod
    def _dispatch_plan(experts, gates, E, cap):
        """Capacity bookkeeping for one routing group, shared by the
        expert-parallel dispatch and the dense capacity reference so both
        drop EXACTLY the same units.

        Units are the k-major flattening of (token, choice) pairs —
        every token's first choice claims capacity before any second
        choice (GShard dispatch priority). Returns (unit_expert [K*t],
        unit_gate [K*t], pos_in_e [K*t] 0-based slot within the expert's
        capacity buffer, keep [K*t])."""
        unit_expert = experts.T.reshape(-1)
        unit_gate = gates.T.reshape(-1)
        onehot = jax.nn.one_hot(unit_expert, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot            # 1-based
        pos_in_e = jnp.sum(pos, axis=-1) - 1
        keep = pos_in_e < cap
        return unit_expert, unit_gate, pos_in_e, keep

    def group_capacity(self, tokens_per_group: int) -> int:
        """Per-expert capacity for one routing group (Switch §2.2:
        tokens/experts * k * capacity_factor, per group)."""
        return max(1, int(math.ceil(
            tokens_per_group / self.E * self.top_k *
            self.capacity_factor)))

    # -- dense single-device reference ----------------------------------
    def apply(self, params, input, ctx: ApplyContext):
        return self._dense(params, input)[0]

    def _dense(self, params, input):
        shape = input.shape
        x2d = input.reshape(-1, self.d)
        experts, gates, probs = self._gates(params, x2d)
        # run every expert on every token, select by routing (dense ref)
        h = jnp.einsum("td,edh->teh", x2d, params["w1"]) + params["b1"]
        h = jnp.maximum(h, 0.0)
        y_all = jnp.einsum("teh,ehd->ted", h, params["w2"]) + params["b2"]
        y = jnp.zeros_like(x2d)
        for k in range(self.top_k):  # static tiny loop
            onehot = jax.nn.one_hot(experts[:, k], self.E, dtype=x2d.dtype)
            y = y + gates[:, k, None] * jnp.einsum("ted,te->td", y_all,
                                                   onehot)
        return y.reshape(shape), (experts, probs)

    def apply_with_aux(self, params, input):
        """(output, aux) — aux carries the Switch load-balancing loss and
        routing statistics. Add `weight * aux['aux_loss']` to the training
        objective; it is minimized (value 1.0) at perfectly uniform
        routing and grows as the router collapses onto few experts."""
        y, (experts, probs) = self._dense(params, input)
        # f_e: fraction of tokens whose TOP-1 choice is e (Switch §2.2);
        # P_e: mean router probability mass on e
        top1 = experts[:, 0]
        f = jnp.mean(jax.nn.one_hot(top1, self.E, dtype=probs.dtype),
                     axis=0)
        p = jnp.mean(probs, axis=0)
        aux_loss = self.E * jnp.sum(f * p)
        entropy = -jnp.sum(f * jnp.log(f + 1e-9))
        return y, {"aux_loss": aux_loss, "expert_fraction": f,
                   "load_entropy": entropy,
                   "max_load": jnp.max(f)}

    def dense_capacity_apply(self, params, x, n_groups: int = 1,
                             return_mask: bool = False):
        """Single-device reference WITH Switch capacity semantics.

        Tokens split into `n_groups` routing groups matching the
        per-device groups of `expert_parallel_apply` on an n_groups-wide
        'expert' axis: same per-group capacity, same k-major dispatch
        priority, same zero contribution for dropped units. This is the
        oracle the EP path must match EXACTLY (kept units and outputs) at
        ANY capacity_factor — unlike `apply`, which is capacity-free and
        only matches when nothing drops.

        Returns output, or (output, keep_mask [K, T]) with
        `return_mask=True`.
        """
        shape = x.shape
        x2d = x.reshape(-1, self.d)
        T = x2d.shape[0]
        if T % n_groups:
            raise ValueError(f"token count {T} not divisible by "
                             f"n_groups={n_groups}")
        tg = T // n_groups
        cap = self.group_capacity(tg)
        E, K = self.E, self.top_k

        def per_group(xl):
            experts, gates, _ = self._gates(params, xl)
            ue, ug, _, keep = MoE._dispatch_plan(experts, gates, E, cap)
            unit_x = jnp.tile(xl, (K, 1))                     # [K*tg, d]
            # per-unit expert FFN via gathered weights (reference-clear,
            # memory-heavy — this is the oracle, not the fast path)
            h = jnp.maximum(
                jnp.einsum("td,tdh->th", unit_x, params["w1"][ue])
                + params["b1"][ue], 0.0)
            y_unit = jnp.einsum("th,thd->td", h, params["w2"][ue]) \
                + params["b2"][ue]
            y_unit = jnp.where(keep[:, None], ug[:, None] * y_unit, 0.0)
            return jnp.sum(y_unit.reshape(K, tg, self.d), axis=0), \
                keep.reshape(K, tg)

        y, keep = jax.vmap(per_group)(x2d.reshape(n_groups, tg, self.d))
        y = y.reshape(shape)
        if return_mask:
            # [n_groups, K, tg] -> [K, T] in token order
            mask = jnp.moveaxis(keep, 1, 0).reshape(self.top_k, T)
            return y, mask
        return y

    # -- expert-parallel execution --------------------------------------
    def expert_parallel_apply(self, mesh: Mesh, params, x,
                              return_mask: bool = False):
        """Run with experts sharded over mesh axis 'expert' (one or more
        experts per device; E divisible by the axis size). Tokens exchange
        with all_to_all; overflow beyond each expert's capacity drops to a
        zero contribution (Switch-Transformer semantics — the dense
        reference matches on tokens within capacity). top_k routing
        dispatches each (token, choice) pair as its own routing unit."""
        E, K = self.E, self.top_k
        n_dev = int(dict(zip(mesh.axis_names,
                             mesh.devices.shape)).get("expert", 0))
        if n_dev == 0 or E % n_dev:
            raise ValueError(
                f"mesh 'expert' axis must divide n_experts={E}")
        shape = x.shape
        x2d = x.reshape(-1, self.d)
        T = x2d.shape[0]
        if T % n_dev:
            raise ValueError(f"token count {T} not divisible by the "
                             f"'expert' axis size {n_dev}")
        # Switch/Mesh-TF capacity is PER GROUP (this device's tokens), so
        # buffers and all_to_all volume shrink as devices are added
        cap = self.group_capacity(T // n_dev)
        moe = self

        def mapped(params_local, x_local):
            # params_local: this device's slice of each stacked expert
            # leaf [E/n_dev, ...]; router is replicated
            t_local = x_local.shape[0]
            experts, gates, _ = moe._gates(
                {"router": params_local["router"]}, x_local)
            unit_expert, unit_gate, pos_in_e, keep = MoE._dispatch_plan(
                experts, gates, E, cap)
            unit_x = jnp.tile(x_local, (K, 1))          # [K*t, d]
            # dispatch buffer [E, cap, d]
            disp = jnp.zeros((E, cap, moe.d), x_local.dtype)
            disp = disp.at[unit_expert,
                           jnp.clip(pos_in_e, 0, cap - 1)].add(
                jnp.where(keep[:, None], unit_x, 0.0))
            recv = lax.all_to_all(disp, "expert", split_axis=0,
                                  concat_axis=0, tiled=True)
            e_local = E // n_dev
            recv = recv.reshape(n_dev, e_local, cap, moe.d)
            out = jnp.zeros_like(recv)
            for le in range(e_local):  # static tiny loop over local experts
                tokens = recv[:, le].reshape(-1, moe.d)
                y = moe._expert_ffn(params_local, le, tokens)
                out = out.at[:, le].set(y.reshape(n_dev, cap, moe.d))
            # send results back to the token owners
            back = lax.all_to_all(
                out.reshape(E, cap, moe.d), "expert",
                split_axis=0, concat_axis=0, tiled=True)
            # gather each kept unit's result from its (expert, pos) slot
            safe_pos = jnp.clip(pos_in_e, 0, cap - 1)
            y_unit = back[unit_expert, safe_pos]
            y_unit = jnp.where(keep[:, None], y_unit, 0.0)
            y_unit = unit_gate[:, None] * y_unit
            return (jnp.sum(y_unit.reshape(K, t_local, moe.d), axis=0),
                    keep.reshape(K, t_local))

        param_specs = {
            "router": P(),
            "w1": P("expert"), "b1": P("expert"),
            "w2": P("expert"), "b2": P("expert"),
        }
        mapped_fn = jax.shard_map(
            mapped, mesh=mesh,
            in_specs=(param_specs, P("expert")),  # tokens split over axis
            out_specs=(P("expert"), P(None, "expert")))
        y, mask = mapped_fn(params, x2d)
        if return_mask:
            return y.reshape(shape), mask
        return y.reshape(shape)
