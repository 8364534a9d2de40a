"""Plain reference for ``ppo_atari84``: the actor-critic forward pass,
generalised advantage estimation and the clipped PPO loss, in float32
``jax.numpy`` at the highest matmul precision.  Independent of ``ray_tpu``:
it reads the weights out of the program's parameter tree and nothing else.

The network is the program's own, not a published one: the three filter
banks of Mnih et al. 2015 (32x8x8/4, 64x4x4/2, 64x3x3/1 on frames scaled to
[0, 1]) but padded 'SAME' as ``flax.linen.Conv`` does by default (maps 21x21,
11x11, 11x11 and 7744 features, where Mnih's VALID convolutions give 20, 9, 7
and 3136), and a dense layer of 256 where Mnih has 512.  The reference
follows the program here because it has to read the program's weights;
``configs/ppo_atari84.json`` sets the published sizes beside these.  GAE is
Schulman et al. 2016, the clipped surrogate Schulman et al. 2017, with
RLlib's clipped value loss.
"""
import jax
import jax.numpy as jnp
import numpy as np


def _conv(x, p, stride):
    y = jax.lax.conv_general_dilated(
        x, p["kernel"].astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["bias"])


def forward(params, obs):
    """obs [B, 84, 84, 4] uint8 -> (logits [B, A], value [B])."""
    with jax.default_matmul_precision("highest"):
        p = params["params"] if "params" in params else params
        trunk = p["NatureCNN_0"]
        x = obs.astype(jnp.float32) / 255.0
        for name, stride in (("Conv_0", 4), ("Conv_1", 2), ("Conv_2", 1)):
            x = _conv(x, trunk[name], stride)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ trunk["Dense_0"]["kernel"]
                        + trunk["Dense_0"]["bias"])
        logits = x @ p["pi"]["kernel"] + p["pi"]["bias"]
        value = (x @ p["vf"]["kernel"] + p["vf"]["bias"])[:, 0]
        return logits, value


def gae(rewards, values, dones, last_value, gamma, lam):
    """Time-major [T, N] numpy arrays -> (advantages, value targets)."""
    rewards, values = np.asarray(rewards, np.float64), np.asarray(
        values, np.float64)
    adv = np.zeros_like(values)
    nxt, acc = np.asarray(last_value, np.float64), 0.0
    for t in range(len(rewards) - 1, -1, -1):
        nt = 1.0 - np.asarray(dones[t], np.float64)
        delta = rewards[t] + gamma * nxt * nt - values[t]
        acc = delta + gamma * lam * nt * acc
        adv[t], nxt = acc, values[t]
    return adv, adv + values


def loss_terms(params, batch, clip, vf_clip):
    """The clipped surrogate's terms on one minibatch: policy loss, value
    loss and entropy."""
    logits, value = forward(params, batch["obs"])
    logp_all = jax.nn.log_softmax(logits, -1)
    logp = jnp.take_along_axis(
        logp_all, batch["actions"][:, None].astype(jnp.int32), -1)[:, 0]
    ratio = jnp.exp(logp - batch["action_logp"])
    adv = batch["advantages"]
    surr = jnp.minimum(ratio * adv,
                       jnp.clip(ratio, 1 - clip, 1 + clip) * adv)
    vf = jnp.minimum((value - batch["value_targets"]) ** 2, vf_clip ** 2)
    return {"policy_loss": -jnp.mean(surr), "vf_loss": 0.5 * jnp.mean(vf),
            "entropy": -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, -1))}
