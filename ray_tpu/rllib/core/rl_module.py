"""RLModule: the model abstraction of the new stack (reference:
rllib/core/rl_module/rl_module.py; jax skeleton the reference already
sketches: rllib/models/jax/).  A module bundles policy + value heads and
exposes forward_inference / forward_exploration / forward_train."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.mlp import MLP
from ray_tpu.models.nature_cnn import (MinAtarCNN, NatureCNN, folds_tiled,
                                       pack_frames, pack_frames_tiled,
                                       packed_shape)


@dataclasses.dataclass(frozen=True)
class RLModuleSpec:
    obs_dim: Optional[int] = None
    obs_shape: Optional[Tuple[int, ...]] = None  # set for pixel obs
    num_actions: int = 2
    hiddens: Tuple[int, ...] = (64, 64)
    conv: bool = False

    def build(self) -> "DiscreteActorCritic":
        return DiscreteActorCritic(self)

    @property
    def packed_obs_shape(self) -> Optional[Tuple[int, ...]]:
        """The shape of one frame as NatureCNN's first layer reads it
        (``nature_cnn.pack_frames``); None where the trunk is another one
        (flat observations, boards under 32 pixels)."""
        if not self.conv or min(self.obs_shape[:2]) < 32:
            return None
        return packed_shape(self.obs_shape)

    @property
    def packs_tiled(self) -> bool:
        """Whether ``pack_obs_tiled`` has a kernel for this spec's frames:
        packed frames that fold as words (``nature_cnn.folds_tiled``)."""
        return self.packed_obs_shape is not None \
            and folds_tiled(self.obs_shape)

    def example_obs(self, batch: int = 1) -> np.ndarray:
        """A zero observation batch matching this spec's trunk input —
        uint8 frames for the conv trunk (NatureCNN does the /255), flat
        float32 vectors otherwise.  The one place example-obs shape/dtype
        selection lives (actor-mode learner init uses this)."""
        if self.conv:
            return np.zeros((batch,) + tuple(self.obs_shape), np.uint8)
        return np.zeros((batch, self.obs_dim), np.float32)

    @classmethod
    def for_env(cls, env, hiddens: Tuple[int, ...]) -> "RLModuleSpec":
        """The one place pixel-vs-flat trunk selection lives: envs with
        an obs_shape get the CNN trunk, flat envs the MLP (shared by the
        PPO and V-trace families' anakin setups)."""
        obs_shape = getattr(env, "obs_shape", None)
        if obs_shape is not None:
            return cls(obs_shape=tuple(obs_shape),
                       num_actions=env.num_actions, conv=True)
        return cls(obs_dim=env.obs_dim, num_actions=env.num_actions,
                   hiddens=tuple(hiddens))


class DiscreteActorCritic(nn.Module):
    """Categorical policy + value baseline (separate heads, shared trunk for
    pixels, separate trunks for vectors — matching RLlib PPO defaults).

    Frames of 32 pixels or more may come raw (``spec.obs_shape``), packed
    (``spec.packed_obs_shape``, what ``pack_obs`` returns) or packed with
    the batch last (``[*spec.packed_obs_shape, B]``, what a minibatch
    gathered by ``ops.gather_rows`` is, and ``pack_obs_tiled``'s second
    result): the static shape tells which, and raw frames are packed first,
    so all run the same convolution."""

    spec: RLModuleSpec

    def pack_obs(self, obs):
        """Observations as the trunk reads them: frames for NatureCNN packed
        (uint8 stays uint8), anything else as it is.  For a caller that
        keeps many observations and reads them more than once; behind the
        barrier the copy it keeps and the one the trunk reads are one
        array (without it the TPU compiler lays each out for itself)."""
        if self.spec.packed_obs_shape is None:
            return obs
        return jax.lax.optimization_barrier(pack_frames(obs))

    def pack_obs_tiled(self, obs, into=None, at=0):
        """``pack_obs(obs)`` for a caller that keeps its observations as
        word tiles (``ops.gather_rows``), where ``spec.packs_tiled``:
        ``(tiles, seen)`` from one kernel, ``seen`` with the batch last,
        which ``__call__`` reads as ``pack_obs``'s form
        (``nature_cnn.pack_frames_tiled``)."""
        return pack_frames_tiled(obs, into=into, at=at)

    @nn.compact
    def __call__(self, obs) -> Tuple[jax.Array, jax.Array]:
        s = self.spec
        if s.conv:
            if s.packed_obs_shape is None:
                trunk = MinAtarCNN(out_dim=128)(obs)
            else:
                frame = tuple(obs.shape[1:])
                batch_last = (
                    frame not in (s.packed_obs_shape, tuple(s.obs_shape))
                    and tuple(obs.shape[:-1]) == s.packed_obs_shape)
                packed = batch_last or frame == s.packed_obs_shape
                if not packed and frame != tuple(s.obs_shape):
                    raise ValueError(
                        f"frames of shape {tuple(obs.shape)}: neither "
                        f"[B, ...] of {tuple(s.obs_shape)} or of packed "
                        f"{s.packed_obs_shape}, nor packed with B last")
                trunk = NatureCNN(out_dim=256)(obs, packed=packed,
                                               batch_last=batch_last)
            logits = nn.Dense(s.num_actions, name="pi")(trunk)
            value = nn.Dense(1, name="vf")(trunk)[..., 0]
        else:
            logits = MLP(s.hiddens, s.num_actions, name="pi_mlp")(obs)
            value = MLP(s.hiddens, 1, name="vf_mlp")(obs)[..., 0]
        return logits, value

    # ---- RLModule API ----
    def forward_inference(self, params, obs):
        logits, _ = self.apply(params, obs)
        return jnp.argmax(logits, axis=-1)

    def forward_exploration(self, params, obs, rng):
        logits, value = self.apply(params, obs)
        action = jax.random.categorical(rng, logits)
        logp = jax.nn.log_softmax(logits)
        action_logp = jnp.take_along_axis(logp, action[..., None], -1)[..., 0]
        return action, action_logp, value

    def forward_train(self, params, obs, actions):
        logits, value = self.apply(params, obs)
        logp_all = jax.nn.log_softmax(logits)
        action_logp = jnp.take_along_axis(
            logp_all, actions[..., None].astype(jnp.int32), -1)[..., 0]
        entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
        return action_logp, value, entropy
