"""Gumbel root search with sequential halving (beyond-reference).

Implements the root-action procedure of "Policy improvement by
planning with Gumbel" (Danihelka et al., ICLR 2022; the mctx
`gumbel_muzero_policy`) on top of the wave-parallel batched search:

- Root exploration comes from sampled Gumbel noise on the prior
  logits, NOT Dirichlet noise + visit-count temperature: the m
  highest `g(a) + logits(a)` valid actions become the candidate set.
- **Sequential halving rides the wave structure**: each wave spreads
  its W simulations evenly over the surviving candidates, and after
  every wave the candidate set is halved by
  `g + logits + sigma(qhat)` score — so the number of waves IS the
  number of halving phases, and the whole schedule stays static
  shapes (a (B, A) candidate mask carried through `lax.fori_loop`).
- The played action is the argmax of the final candidates' scores
  (exploration is entirely the Gumbel sample — no temperature), and
  the policy target is the **completed-Q improved policy**
  `softmax(logits + sigma(q_completed))` over valid actions, where
  unvisited actions take the root's network value (a simplification
  of mctx's prior-weighted value mix, documented here).

This beats visit-count PUCT targets at small simulation budgets
because every simulation is spent comparing the few root actions that
matter, and the improved policy is a proper policy-improvement
operator rather than a visit histogram. Enable with
`MCTSConfig.root_selection="gumbel"`.

sigma(q) = (c_visit + max_a N(a)) * c_scale * q  (paper Eq. 8 defaults).
"""

import jax
import jax.numpy as jnp

from ..config.mcts_config import MCTSConfig
from ..telemetry.device_stats import beacon_every, emit_beacon
from .search import BatchedMCTS, SearchOutput


class GumbelMCTS(BatchedMCTS):
    """Wave-parallel search with Gumbel sequential-halving root."""

    def __init__(
        self,
        env,
        extractor,
        model,
        config: MCTSConfig,
        support,
        exploit: bool = False,
    ):
        # Dirichlet root noise is PUCT's exploration mechanism; Gumbel
        # sampling replaces it entirely (paper §3). `exploit` zeroes
        # the Gumbel sample too (deterministic logits + sigma(q)
        # halving/argmax) — playout-cap fast searches must play the
        # best cheap move, not explore.
        super().__init__(
            env,
            extractor,
            model,
            config.model_copy(update={"dirichlet_epsilon": 0.0}),
            support,
        )
        self.m_candidates = config.gumbel_m
        self.c_visit = config.gumbel_c_visit
        self.c_scale = config.gumbel_c_scale
        self.exploit = exploit

    # --- scoring helpers --------------------------------------------------

    def _sigma(self, q: jax.Array, visit_counts: jax.Array) -> jax.Array:
        """Monotone Q transform: (c_visit + max N) * c_scale * q."""
        max_n = visit_counts.max(axis=-1, keepdims=True)
        return (self.c_visit + max_n) * self.c_scale * q

    def _root_q(self, tree) -> tuple[jax.Array, jax.Array]:
        """(q, visits) of the root edges, (B, A) each."""
        visits = tree.e_visits[:, 0, :]
        q = jnp.where(
            visits > 0, tree.e_value[:, 0, :] / jnp.maximum(visits, 1e-9), 0.0
        )
        return q, visits

    # --- the search -------------------------------------------------------

    def _search(self, variables, root_states, rng: jax.Array) -> SearchOutput:
        cfg = self.config
        batch = root_states.done.shape[0]
        a = self.action_dim
        w = self.wave_size
        # Distinct keys for root init and the Gumbel sample: reusing
        # one is harmless only while GumbelMCTS forces
        # dirichlet_epsilon=0 (init never consumes its key); a fourth
        # key keeps root noise and Gumbel perturbations independent if
        # Dirichlet were ever re-enabled.
        rng, init_rng, gumbel_rng, wave_rng = jax.random.split(rng, 4)
        tree = self._init_tree(variables, root_states, init_rng)

        with jax.named_scope("gumbel/root"):
            valid = tree.valid[:, 0, :] > 0  # (B, A)
            logits = jnp.where(
                valid, jnp.log(jnp.maximum(tree.prior[:, 0, :], 1e-12)), -jnp.inf
            )
            g = (
                jnp.zeros((batch, a))
                if self.exploit
                else jax.random.gumbel(gumbel_rng, (batch, a))
            )
            base_score = jnp.where(valid, g + logits, -jnp.inf)  # (B, A)

            # Initial candidates: top-m by g + logits among valid actions.
            # m is clamped to the wave size so EVERY survivor receives at
            # least one simulation per halving phase — otherwise arms could
            # be halved (or even played) on sigma(q)=0 without ever being
            # simulated.
            m0 = min(self.m_candidates, w, a)
            kth = jnp.sort(base_score, axis=-1)[:, -m0][:, None]
            cand = valid & (base_score >= kth)  # (B, A) may hold < m0 rows

        @jax.named_scope("gumbel/root")
        def assign_roots(tree, cand_mask: jax.Array) -> jax.Array:
            """(B, A) mask -> (B, W) member root actions.

            The first `count` members cover every surviving candidate
            once; surplus members repeat the cycle ONLY onto already-
            expanded candidates (their descent then deepens that
            subtree via PUCT). A surplus member aimed at a still-
            unexpanded edge would duplicate the first member's
            expansion wholesale, so it is released (-1 = unforced) to
            a noise-diversified PUCT descent instead.
            """
            order = jnp.argsort(~cand_mask, axis=-1, stable=True)  # (B, A)
            count = jnp.maximum(cand_mask.sum(axis=-1, keepdims=True), 1)
            j = jnp.arange(w)[None, :]  # (1, W)
            slot = j % count  # (B, W)
            roots = jnp.take_along_axis(order, slot, axis=1).astype(
                jnp.int32
            )
            expanded = (
                jnp.take_along_axis(tree.children[:, 0, :], roots, axis=1)
                >= 0
            )
            force = (j < count) | expanded
            return jnp.where(force, roots, -1)

        @jax.named_scope("gumbel/root")
        def halve(tree, cand_mask: jax.Array) -> jax.Array:
            """Keep the better half of the candidates by g+logits+sigma(q)."""
            q, visits = self._root_q(tree)
            score = jnp.where(
                cand_mask, base_score + self._sigma(q, visits), -jnp.inf
            )
            count = cand_mask.sum(axis=-1)
            keep = jnp.maximum((count + 1) // 2, 1)  # ceil(count/2), >= 1
            sorted_scores = jnp.sort(score, axis=-1)  # ascending
            kth = jnp.take_along_axis(
                sorted_scores, (a - keep)[:, None], axis=1
            )
            return cand_mask & (score >= kth)

        def wave_body(k, carry):
            # The search carry is (tree, wasted, base) plus the
            # device-stats histogram tail when enabled (`_stats_seed`);
            # the candidate mask rides behind it and never enters
            # `_wave`.
            *sc, cand_mask = carry
            emit_beacon("search_wave", k, every=beacon_every())
            roots = assign_roots(sc[0], cand_mask)
            sc = self._wave(
                variables,
                batch,
                tuple(sc),
                jax.random.fold_in(wave_rng, k),
                root_action=roots,
            )
            tree = sc[0]
            # Halve after every wave but the last (the final set is
            # resolved by argmax below).
            cand_mask = jax.lax.cond(
                k < self.num_waves - 1,
                lambda: halve(tree, cand_mask),
                lambda: cand_mask,
            )
            return (*sc, cand_mask)

        final = jax.lax.fori_loop(
            0,
            self.num_waves,
            wave_body,
            (tree, jnp.zeros((batch,), jnp.int32), jnp.int32(1))
            + self._stats_seed()
            + (cand,),
        )
        tree, wasted, base = final[0], final[1], final[2]
        stats_tail, cand = final[3:-1], final[-1]

        with jax.named_scope("gumbel/root"):
            q, visits = self._root_q(tree)
            final_score = jnp.where(
                cand, base_score + self._sigma(q, visits), -jnp.inf
            )
            selected = jnp.argmax(final_score, axis=-1).astype(jnp.int32)
            # Terminal roots have no meaningful selection; mirror PUCT's
            # no-visit sentinel so the host-side guard logic stays shared.
            selected = jnp.where(root_states.done, -1, selected)

            # Completed-Q improved policy (paper §4): unvisited actions
            # take the root network value (simplified value mix).
            q_completed = jnp.where(visits > 0, q, tree.root_value0[:, None])
            improved_logits = jnp.where(
                valid, logits + self._sigma(q_completed, visits), -jnp.inf
            )
            any_valid = valid.any(axis=-1, keepdims=True)
            improved = jax.nn.softmax(
                jnp.where(any_valid, improved_logits, 0.0), axis=-1
            )
            improved = jnp.where(valid, improved, 0.0)
            norm = improved.sum(axis=-1, keepdims=True)
            improved = improved / jnp.maximum(norm, 1e-9)

            root_visits = 1.0 + visits.sum(axis=-1)
            root_value = (
                tree.root_value0 + tree.e_value[:, 0, :].sum(axis=-1)
            ) / root_visits
        stats = None
        if self.device_stats:
            stats = self._stat_pack(tree, wasted, base, stats_tail[0], batch)
        return SearchOutput(
            visit_counts=visits,
            root_value=root_value,
            root_prior=tree.prior[:, 0],
            total_simulations=jnp.int32(cfg.max_simulations * batch),
            wasted_slots=wasted,
            selected_action=selected,
            improved_policy=improved,
            stats=stats,
            net_counters=tree.net_counters,
        )
