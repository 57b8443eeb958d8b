"""Round-4 algorithm additions: ARS, QMIX, AlphaZero.

Reference analogs: ``rllib/algorithms/ars/``, ``rllib/algorithms/qmix/``,
``rllib/algorithms/alpha_zero/``.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import rl


@pytest.fixture
def rl_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=6)
    yield ray_tpu
    ray_tpu.shutdown()


# ------------------------------------------------------------------- ARS --

def test_ars_improves_cartpole(rl_cluster):
    """ARS (top-direction selection + obs normalization) must lift
    CartPole returns above the random baseline within a few iterations."""
    cfg = rl.ARSConfig()
    cfg.env_runners(num_env_runners=2)
    cfg.num_perturbations = 8
    cfg.top_directions = 4
    cfg.episodes_per_perturbation = 1
    cfg.max_episode_len = 200
    cfg.hidden = (32,)
    algo = cfg.build()
    first = algo.training_step()["mean_return"]
    best = first
    for _ in range(12):
        best = max(best, algo.training_step()["mean_return"])
    assert best > max(40.0, first), \
        f"ARS did not improve: first={first} best={best}"


def test_ars_filter_syncs_across_fleet(rl_cluster):
    cfg = rl.ARSConfig()
    cfg.env_runners(num_env_runners=2)
    cfg.num_perturbations = 4
    cfg.max_episode_len = 50
    algo = cfg.build()
    algo.training_step()
    # driver accumulated real statistics and broadcast them
    assert algo._f_count > 10
    means = ray_tpu.get([w.set_filter.remote(
        algo._f_sum / algo._f_count, np.ones(algo.spec.obs_dim))
        for w in algo._workers])
    assert means == [None, None]
    # checkpoint round-trips the filter
    state = algo.get_extra_state()
    algo2 = rl.ARSConfig().env_runners(num_env_runners=1).build()
    algo2.set_extra_state(state)
    assert algo2._f_count == algo._f_count


# ------------------------------------------------------------------ QMIX --

def test_qmix_mixer_is_monotonic():
    """dQ_tot/dQ_a >= 0 for every agent — the QMIX factorization
    guarantee (abs-hypernet weights)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.rl.algorithms.qmix import _init_mixer, _mix

    mixer = _init_mixer(jax.random.key(0), n_agents=3, state_dim=5,
                        embed=8)
    state = jnp.asarray(np.random.default_rng(0).normal(size=(4, 5)),
                        dtype=jnp.float32)
    qs = jnp.asarray(np.random.default_rng(1).normal(size=(4, 3)),
                     dtype=jnp.float32)
    jac = jax.vmap(jax.jacobian(lambda q, s: _mix(mixer, q[None], s[None])
                                [0]))(qs, state)
    assert (np.asarray(jac) >= -1e-6).all()


def test_qmix_heterogeneous_action_spaces():
    """Agents with different action counts: exploration and TD targets
    must never touch an agent's invalid action slots."""
    from ray_tpu.rl.env import EnvSpec
    from ray_tpu.rl.multi_agent import MultiAgentEnv

    class Hetero(MultiAgentEnv):
        def __init__(self, num_envs=4, **kw):
            self.agents = ["small", "big"]
            self.num_envs = num_envs
            self.spec = {"small": EnvSpec(obs_dim=3, num_actions=2),
                         "big": EnvSpec(obs_dim=5, num_actions=4)}
            self._t = np.zeros(num_envs, dtype=np.int64)

        def reset(self):
            self._t[:] = 0
            return {"small": np.zeros((self.num_envs, 3), np.float32),
                    "big": np.zeros((self.num_envs, 5), np.float32)}

        def step(self, actions):
            assert actions["small"].max() < 2, actions["small"]
            assert actions["big"].max() < 4, actions["big"]
            self._t += 1
            dones = self._t >= 8
            self._t[dones] = 0
            r = {a: np.ones(self.num_envs, np.float32)
                 for a in self.agents}
            obs = self.reset() if dones.all() else {
                "small": np.zeros((self.num_envs, 3), np.float32),
                "big": np.zeros((self.num_envs, 5), np.float32)}
            return obs, r, dones

    cfg = rl.QMIXConfig()
    cfg.env = Hetero
    cfg.num_envs_per_runner = 4
    cfg.rollout_fragment_length = 16
    cfg.learning_starts = 32
    cfg.updates_per_iter = 4
    algo = rl.QMIX({"__algo_config": cfg})
    for _ in range(2):
        m = algo.step()
    assert "td_abs_mean" in m and np.isfinite(m["td_abs_mean"])


def test_qmix_learns_coordination(rl_cluster):
    """Team reward on CoordinationGame: random play earns ~1/k^2 = 0.11;
    QMIX must coordinate well above that."""
    cfg = rl.QMIXConfig()
    cfg.num_envs_per_runner = 16
    cfg.rollout_fragment_length = 32
    cfg.learning_starts = 256
    cfg.epsilon_decay_steps = 3_000
    cfg.updates_per_iter = 48
    cfg.hidden = (64,)
    cfg.seed = 3
    algo = rl.QMIX({"__algo_config": cfg})
    best = 0.0
    for _ in range(20):
        m = algo.step()
        best = max(best, m["reward_mean_per_step"])
        if best > 0.5:
            break
    assert best > 0.5, f"QMIX stuck at reward/step {best}"
    # checkpoint round-trip
    ckpt = algo.save_checkpoint("")
    algo.load_checkpoint(ckpt)


# ------------------------------------------------------------------ R2D2 --

def test_masked_cartpole_hides_velocity():
    env = rl.MaskedCartPole(4, seed=0)
    assert env.spec.obs_dim == 2
    obs = env.reset()
    assert obs.shape == (4, 2)
    o2, r, d = env.step(np.zeros(4, dtype=np.int64))
    assert o2.shape == (4, 2) and r.shape == (4,)


def test_r2d2_gru_and_sequence_machinery():
    """Smoke: sequences flush at episode boundaries and length cuts,
    the stored h0 rides replay, and the loss masks padding."""
    cfg = rl.R2D2Config()
    cfg.num_envs_per_runner = 4
    cfg.rollout_fragment_length = 48
    cfg.seq_len = 8
    cfg.burn_in = 2
    cfg.learning_starts = 8
    cfg.updates_per_iter = 4
    algo = rl.R2D2({"__algo_config": cfg})
    m = algo.step()
    assert m["buffer_sequences"] >= 8
    assert "td_abs_mean" in m and np.isfinite(m["td_abs_mean"])
    # stored sequences carry the right shapes
    mb = algo.buffer.sample(4)
    assert mb["obs"].shape == (4, 8, 2)
    assert mb["h0"].shape == (4, cfg.gru_hidden)
    assert set(np.unique(mb["valid"])) <= {0.0, 1.0}
    # evaluate is greedy + fresh state, and round-trips a checkpoint
    ev = algo.evaluate(num_episodes=2)
    assert ev["episodes"] >= 2
    ckpt = algo.save_checkpoint("")
    algo.load_checkpoint(ckpt)


@pytest.mark.slow
def test_r2d2_learns_masked_cartpole():
    """Memoryless policies plateau ~40-60 on velocity-masked CartPole;
    recurrence must beat that decisively."""
    cfg = rl.R2D2Config()
    cfg.num_envs_per_runner = 16
    cfg.rollout_fragment_length = 64
    cfg.seed = 1
    algo = rl.R2D2({"__algo_config": cfg})
    best = 0.0
    for _ in range(100):
        m = algo.step()
        best = max(best, m.get("episode_return_mean", 0.0))
        if best > 90:
            break
    assert best > 90, f"R2D2 plateaued at {best}"


# ------------------------------------------------------------- AlphaZero --

def _play_vs_random(algo, games: int, seed: int, az_first: bool) -> float:
    """Returns AlphaZero's score in [0,1] (win=1, draw=0.5)."""
    rng = np.random.default_rng(seed)
    game = algo.game
    score = 0.0
    for g in range(games):
        state = game.initial_state()
        az_turn = az_first
        while True:
            tv = game.terminal_value(state)
            if tv is not None:
                # tv is for the player to move; the player who JUST moved
                # sees -tv
                just_moved_was_az = not az_turn
                val = -tv if just_moved_was_az else tv
                score += {1.0: 1.0, 0.0: 0.5, -1.0: 0.0}[val]
                break
            if az_turn:
                a = algo.policy_action(state, greedy=True)
            else:
                legal = np.nonzero(game.legal_actions(state))[0]
                a = int(rng.choice(legal))
            state = game.next_state(state, a)
            az_turn = not az_turn
    return score / games


def test_tictactoe_rules():
    game = rl.TicTacToe()
    s = game.initial_state()
    assert game.terminal_value(s) is None
    assert game.legal_actions(s).sum() == 9
    # X plays 0,1,2 (top row) while O plays 3,4
    for a in (0, 3, 1, 4, 2):
        s = game.next_state(s, a)
    # X completed the top row; O (to move) has lost
    assert game.terminal_value(s) == -1.0
    enc = game.encode(s)
    assert enc.shape == (18,)
    # own-plane for O marks squares 3,4
    assert enc[3] == 1.0 and enc[4] == 1.0 and enc[0] == 0.0


def test_mcts_finds_winning_move():
    """With a uniform prior and no net signal, enough simulations must
    still find the immediate winning move (pure search)."""
    game = rl.TicTacToe()

    def uniform_predict(obs):
        return np.ones(9) / 9, 0.0

    # X: 0,1 placed; O: 3,4. X to move — 2 wins immediately.
    s = game.initial_state()
    for a in (0, 3, 1, 4):
        s = game.next_state(s, a)
    mcts = rl.MCTS(game, uniform_predict, noise_eps=0.0,
                   rng=np.random.default_rng(0))
    visits = mcts.search(s, 256, root_noise=False)
    assert int(np.argmax(visits)) == 2, visits


def test_alphazero_beats_random():
    cfg = rl.AlphaZeroConfig()
    cfg.num_simulations = 24
    cfg.games_per_iter = 24
    cfg.hidden = (64, 64)
    cfg.seed = 0
    algo = rl.AlphaZero({"__algo_config": cfg})
    for _ in range(12):
        algo.step()
    score_first = _play_vs_random(algo, 20, seed=1, az_first=True)
    score_second = _play_vs_random(algo, 20, seed=2, az_first=False)
    # a competent player never loses moving first and rarely as second
    assert score_first >= 0.9, score_first
    assert score_second >= 0.7, score_second
