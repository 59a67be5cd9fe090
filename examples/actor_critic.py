#!/usr/bin/env python
"""Actor-critic policy gradient through Gluon autograd (ref role:
example/gluon/actor_critic.py — shared trunk, policy + value heads,
REINFORCE with the critic as baseline).

Environment is a self-contained numpy cartpole-like balancing task
(zero-egress: no gym).  State is (x, x_dot, theta, theta_dot); the
pole falls unless the agent pushes the cart under it; episodes end
on |theta| > 12 deg, |x| > 2.4, or 200 steps.  An untrained policy
survives ~20 steps; a trained one balances for the full horizon.

Episodes end after 10 to 200 steps, and a device that compiles one
program per shape would compile every operator of the update anew for
each new length: so every episode is padded to --max-steps and its
means are taken under a weight of 1/n on the n real steps, and the
net is hybridized, which leaves one program for the environment step
and one for the update.

--quick is the CI gate (150 episodes): mean episode length over the
last 10 episodes must be at least 3x the first-10 mean.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class CartPole:
    """Classic Barto-Sutton-Anderson dynamics, Euler-integrated."""
    G, MC, MP, L, F, TAU = 9.8, 1.0, 0.1, 0.5, 10.0, 0.02
    THETA_MAX = 12 * np.pi / 180
    X_MAX = 2.4

    def __init__(self, rs):
        self.rs = rs
        self.s = None

    def reset(self):
        self.s = self.rs.uniform(-0.05, 0.05, 4).astype(np.float32)
        return self.s.copy()

    def step(self, action):
        x, xd, th, thd = self.s
        f = self.F if action == 1 else -self.F
        mt = self.MC + self.MP
        pml = self.MP * self.L
        ct, st = np.cos(th), np.sin(th)
        tmp = (f + pml * thd ** 2 * st) / mt
        tha = (self.G * st - ct * tmp) / (
            self.L * (4.0 / 3.0 - self.MP * ct ** 2 / mt))
        xa = tmp - pml * tha * ct / mt
        x, xd = x + self.TAU * xd, xd + self.TAU * xa
        th, thd = th + self.TAU * thd, thd + self.TAU * tha
        self.s = np.array([x, xd, th, thd], np.float32)
        done = (abs(x) > self.X_MAX or abs(th) > self.THETA_MAX)
        return self.s.copy(), 1.0, done


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="Gluon actor-critic")
    p.add_argument("--episodes", type=int, default=300)
    p.add_argument("--gamma", type=float, default=0.99)
    p.add_argument("--lr", type=float, default=2e-2)
    p.add_argument("--max-steps", type=int, default=200)
    p.add_argument("--quick", action="store_true",
                   help="CI mode: short run + reward gate")
    return p.parse_args(argv)


def main(argv=None):
    from incubator_mxnet_tpu.utils.platform import maybe_force_cpu
    maybe_force_cpu()
    args = parse_args(argv)
    if args.quick:
        args.episodes = 150

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, nd
    from incubator_mxnet_tpu.gluon import nn

    class ActorCritic(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.trunk = nn.Dense(64, activation="relu")
                self.policy = nn.Dense(2)
                self.value = nn.Dense(1)

        def hybrid_forward(self, F, x):
            h = self.trunk(x)
            return self.policy(h), self.value(h)

    mx.random.seed(0)
    rs = np.random.RandomState(0)
    env = CartPole(rs)

    net = ActorCritic(prefix="ac_")
    net.initialize(mx.init.Xavier())
    net.hybridize()     # one program for the step, one for the update
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})

    lengths = []
    for ep in range(args.episodes):
        s = env.reset()
        states, actions, rewards = [], [], []
        for _ in range(args.max_steps):
            logits, _ = net(nd.array(s[None]))
            p = np.asarray(
                mx.nd.softmax(logits).asnumpy()).ravel()
            a = int(rs.choice(2, p=p / p.sum()))
            states.append(s)
            actions.append(a)
            s, r, done = env.step(a)
            rewards.append(r)
            if done:
                break
        lengths.append(len(rewards))

        # discounted returns, normalized
        ret = np.zeros(len(rewards), np.float32)
        acc = 0.0
        for t in reversed(range(len(rewards))):
            acc = rewards[t] + args.gamma * acc
            ret[t] = acc
        ret = (ret - ret.mean()) / (ret.std() + 1e-6)

        # every episode padded to max_steps: the means over the
        # episode's n steps become sums under weights of 1/n and 0
        n = len(rewards)
        xs = np.zeros((args.max_steps, 4), np.float32)
        xs[:n] = states
        onehot = np.zeros((args.max_steps, 2), np.float32)
        onehot[np.arange(n), actions] = 1.0
        rets = np.zeros(args.max_steps, np.float32)
        rets[:n] = ret
        weight = np.zeros(args.max_steps, np.float32)
        weight[:n] = 1.0 / n
        xs, onehot, rets, weight = (nd.array(a) for a in
                                    (xs, onehot, rets, weight))
        with autograd.record():
            logits, values = net(xs)
            logp = mx.nd.log_softmax(logits)
            chosen = (logp * onehot).sum(axis=1)
            adv = rets - values.reshape(-1)
            # critic baseline enters the actor term detached
            actor = -(chosen * adv.detach() * weight).sum()
            critic = (adv ** 2 * weight).sum()
            loss = actor + 0.5 * critic
        loss.backward()
        trainer.step(1)
        if ep % 25 == 0:
            print(f"episode {ep}: len={lengths[-1]} "
                  f"avg10={np.mean(lengths[-10:]):.1f}", flush=True)

    first10 = float(np.mean(lengths[:10]))
    last10 = float(np.mean(lengths[-10:]))
    summary = dict(episodes=args.episodes, first10=first10,
                   last10=last10, best=int(max(lengths)))
    print(json.dumps(summary))
    if args.quick:
        assert last10 > 3 * first10, (first10, last10)
    return summary


if __name__ == "__main__":
    main()
