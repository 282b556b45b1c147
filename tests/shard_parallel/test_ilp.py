"""ILP / greedy-fallback unit tests (no device work but the last class's
compile of a backward stage in small for two virtual devices).

The greedy fallback must enforce ``memory_budget_per_device`` as hard as
the MILP does (ref auto_sharding's memory constraint) — an OOM layout
must never be silently "chosen".
"""
import numpy as np
import pytest

from alpa_tpu.shard_parallel.ilp import (InfeasibleMemoryBudget,
                                         _solve_greedy, solve_strategy_graph)
from alpa_tpu.shard_parallel.strategy import (Edge, Node, Strategy,
                                              StrategyGraph)


def _invar_node(idx, mem_options):
    """An invar node with (replicated, sharded) strategies: the replicated
    one is comm-free but heavy; the sharded one costs comm but is light."""
    strategies = [
        Strategy(name=f"s{k}", out_spec=(), comm_cost=float(k),
                 mem_bytes=float(m))
        for k, m in enumerate(mem_options)
    ]
    return Node(idx=idx, kind="invar", aval=None, strategies=strategies,
                invar_idx=idx)


def _graph(nodes, edges=()):
    return StrategyGraph(list(nodes), list(edges), None)


class TestGreedyMemoryBudget:

    def test_budget_respected(self):
        # replicated = 100 B (cost 0), sharded = 10 B (cost 1) per node;
        # budget 50 forces sharded everywhere despite higher comm cost.
        g = _graph([_invar_node(i, [100, 10]) for i in range(4)])
        choice = _solve_greedy(g, [2] * 4, memory_budget=50)
        used = sum(g.nodes[i].strategies[choice[i]].mem_bytes
                   for i in range(4))
        assert used <= 50, (choice, used)
        assert choice == [1, 1, 1, 1]

    def test_partial_budget_picks_cheapest_mix(self):
        # budget lets exactly one node stay replicated
        g = _graph([_invar_node(i, [100, 10]) for i in range(4)])
        choice = _solve_greedy(g, [2] * 4, memory_budget=130)
        used = sum(g.nodes[i].strategies[choice[i]].mem_bytes
                   for i in range(4))
        assert used <= 130, (choice, used)
        assert sum(1 for c in choice if c == 0) == 1

    def test_infeasible_raises(self):
        g = _graph([_invar_node(i, [100, 10]) for i in range(4)])
        with pytest.raises(InfeasibleMemoryBudget):
            _solve_greedy(g, [2] * 4, memory_budget=30)

    def test_infeasible_propagates_through_driver(self):
        g = _graph([_invar_node(i, [100, 10]) for i in range(4)])
        with pytest.raises(InfeasibleMemoryBudget):
            solve_strategy_graph(g, time_limit=1, memory_budget=30)

    def test_refinement_cannot_break_budget(self):
        # An edge strongly prefers node 1 replicated; the budget forbids
        # both nodes replicated — refinement must not flip into OOM.
        n0 = _invar_node(0, [100, 10])
        n1 = _invar_node(1, [100, 10])
        cost = np.array([[0.0, 500.0], [500.0, 500.0]])
        g = _graph([n0, n1], [Edge(0, 1, cost)])
        choice = _solve_greedy(g, [2, 2], memory_budget=120)
        used = sum(g.nodes[i].strategies[choice[i]].mem_bytes
                   for i in (0, 1))
        assert used <= 120, (choice, used)


def _backward_stage(x, dy, acc):
    """The shape of a backward stage's tail: a weight gradient's product,
    summed into its accumulator."""
    return x.T @ dy + acc


class TestDonatedAccumulator:
    """``alias_pairs``: a sum written into a donated accumulator's buffer
    leaves with the accumulator's sharding, and the way there is on the
    objective (ref auto_sharding.py:771-823, the alias constraints)."""

    PAIRS = [(2, 0)]

    def _plan(self, alias_pairs, **kwargs):
        import jax
        import jax.numpy as jnp
        from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
        from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
        from alpa_tpu.shard_parallel.solver import plan_auto_sharding
        avals = [jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in ((512, 256), (512, 1024), (256, 1024))]
        opt = AutoShardingOption(logical_mesh_shape=(1, 2),
                                 constrain_min_elements=0)
        mesh = LocalPhysicalDeviceMesh(jax.devices()[:2])
        return avals, plan_auto_sharding(
            _backward_stage, avals, [""] * 3, [], mesh, opt,
            alias_pairs=alias_pairs, **kwargs)

    @staticmethod
    def _only_replicated(graph, node_idx):
        """Make an invar node a one-strategy node (its replicated spec)."""
        from alpa_tpu.shard_parallel.sharding_spec import is_replicated
        node = graph.nodes[node_idx]
        keep = [k for k, st in enumerate(node.strategies)
                if is_replicated(st.out_spec)]
        node.strategies = [node.strategies[k] for k in keep]
        for e in graph.edges:
            if e.src == node_idx:
                e.cost = e.cost[keep, :]
            if e.dst == node_idx:
                e.cost = e.cost[:, keep]

    @pytest.mark.parametrize("case", ["paired", "held_replicated",
                                      "unpaired"])
    def test_sum_and_accumulator(self, case):
        import jax
        from alpa_tpu.shard_parallel.ilp import solution_cost
        from alpa_tpu.shard_parallel.sharding_spec import (is_replicated,
                                                           resharding_cost)
        from alpa_tpu.shard_parallel.solver import alias_stats
        from alpa_tpu.shard_parallel.strategy import map_spec

        pairs = [] if case == "unpaired" else self.PAIRS
        avals, (_, in_sh, cfn, _, (graph, choice)) = self._plan(
            pairs, return_graph=True)
        acc = next(n for n in graph.nodes if n.invar_idx == 2)
        dot = next(n for n in graph.nodes if n.label.startswith("dot"))

        def product_spec(choice):
            _, dimmap, _ = graph.alias_edges[0]
            return map_spec(dot.strategies[choice[dot.idx]].out_spec,
                            dimmap, 2)[0]

        if case == "unpaired":
            # what the graph and the choice are without pairs: no edge
            # into the accumulator but the sum's consistency edge, the
            # accumulator replicated and sliced for nothing
            assert graph.alias_edges == []
            assert [(e.src, e.dst) for e in graph.edges
                    if acc.idx in (e.src, e.dst)] == [(acc.idx, dot.idx)]
            assert is_replicated(acc.strategies[choice[acc.idx]].out_spec)
            assert not is_replicated(
                dot.strategies[choice[dot.idx]].out_spec)
            assert solution_cost(graph, choice) == 0.0
            return

        assert [(s, d) for s, _, d in graph.alias_edges] == \
            [(dot.idx, acc.idx)]
        if case == "paired":
            spec = acc.strategies[choice[acc.idx]].out_spec
            assert not is_replicated(spec)
            assert spec == product_spec(choice)
            assert solution_cost(graph, choice) == 0.0
            assert alias_stats(graph, choice) == {
                "alias_pairs": 1, "alias_sharded": 1,
                "alias_reshard_bytes": 0}
            # and the compiled program gathers no sum: the output locked
            # to the accumulator's sharding, as the pipeshard stage does
            fun = cfn or _backward_stage
            hlo = jax.jit(fun, in_shardings=in_sh, out_shardings=in_sh[2],
                          donate_argnums=(2,)).lower(*avals).compile() \
                .as_text()
            assert "all-gather" not in hlo, hlo
        else:
            self._only_replicated(graph, acc.idx)
            from alpa_tpu.shard_parallel.ilp import solve_strategy_graph
            choice = solve_strategy_graph(graph)
            want = resharding_cost(acc.aval, product_spec(choice),
                                   acc.strategies[0].out_spec,
                                   graph.logical_mesh)
            assert want > 0
            assert solution_cost(graph, choice) == want
            assert alias_stats(graph, choice) == {
                "alias_pairs": 1, "alias_sharded": 0,
                "alias_reshard_bytes": 256 * 1024 * 4 // 2}

    def test_given_input_has_one_strategy(self):
        """``fixed_in``: an input the caller compiles with a sharding of
        another program's choosing is planned as given."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        _, (mesh, in_sh, _, _, _) = self._plan(self.PAIRS,
                                               return_graph=True)
        given = NamedSharding(mesh, PartitionSpec("mesh1"))
        assert in_sh[2].spec != given.spec
        _, (_, in_sh, _, _, (graph, choice)) = self._plan(
            self.PAIRS, fixed_in={2: given}, return_graph=True)
        acc = next(n for n in graph.nodes if n.invar_idx == 2)
        assert [st.out_spec for st in acc.strategies] == [((1,), ())]
        assert in_sh[2].spec == given.spec

    def test_pairs_are_in_the_cache_key(self):
        """A cached choice of the graph without the pairs' edges has the
        same number of nodes: it must not replay for the one with."""
        from alpa_tpu.compile_cache import get_compile_cache
        from alpa_tpu.global_env import global_config
        prev = global_config.compile_cache_enabled
        global_config.compile_cache_enabled = True

        def ilp_stats():
            return dict(get_compile_cache().stats()["namespaces"]["ilp"])

        try:
            _, (_, without, _, _) = self._plan([])
            first = ilp_stats()
            _, (_, with_pairs, _, _) = self._plan(self.PAIRS)
            second = ilp_stats()
            _, (_, again, _, _) = self._plan(self.PAIRS)
            third = ilp_stats()
        finally:
            global_config.compile_cache_enabled = prev
        assert (first["misses"], first["hits"]) == (1, 0)
        assert (second["misses"], second["hits"]) == (2, 0)
        assert (third["misses"], third["hits"]) == (2, 1)
        assert without[2].spec != with_pairs[2].spec
        assert again[2].spec == with_pairs[2].spec


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
