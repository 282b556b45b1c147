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


def _head_backward(logits, hidden, table):
    """The shape of a head's backward: the logits, as the forward stage
    left them, through an elementwise chain into the table's product."""
    import jax.numpy as jnp
    grad = jnp.exp(logits) * 0.5
    return grad.T @ hidden + table


class TestGivenInput:
    """``fixed_in``: a value another program of the mesh decided arrives
    as it was decided, and the plan pays for whatever else its products
    want (ISSUE 52)."""

    SHAPES = ((512, 1024), (512, 256), (1024, 256))

    def _plan(self, fixed_in=None, **kwargs):
        import jax
        import jax.numpy as jnp
        from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
        from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
        from alpa_tpu.shard_parallel.solver import plan_auto_sharding
        avals = [jax.ShapeDtypeStruct(s, jnp.float32) for s in self.SHAPES]
        opt = AutoShardingOption(logical_mesh_shape=(1, 2),
                                 constrain_min_elements=0)
        mesh = LocalPhysicalDeviceMesh(jax.devices()[:2])
        return plan_auto_sharding(_head_backward, avals, [""] * 3, [],
                                  mesh, opt, fixed_in=fixed_in, **kwargs)

    @staticmethod
    def _only(graph, node, name):
        """Leave a node the one strategy called ``name``."""
        keep = [k for k, st in enumerate(node.strategies)
                if st.name == name]
        assert len(keep) == 1, [st.name for st in node.strategies]
        node.strategies = [node.strategies[k] for k in keep]
        for e in graph.edges:
            if e.src == node.idx:
                e.cost = e.cost[keep, :]
            if e.dst == node.idx:
                e.cost = e.cost[:, keep]

    def _costs(self, fixed_in):
        """The objective with the product left to the solver, and with it
        held to the strategy that wants the chain's value whole."""
        from alpa_tpu.shard_parallel.ilp import (solution_cost,
                                                 solve_strategy_graph)
        *_, (graph, choice) = self._plan(fixed_in, return_graph=True)
        dot = next(n for n in graph.nodes if n.label.startswith("dot"))
        free = solution_cost(graph, choice), dot.strategies[
            choice[dot.idx]].name
        self._only(graph, dot, "j0@1")
        return graph, free, solution_cost(graph,
                                          solve_strategy_graph(graph))

    def test_the_strategy_that_slices_a_given_operand_wins_by_a_price(self):
        from jax.sharding import NamedSharding, PartitionSpec
        from alpa_tpu.shard_parallel.solver import given_stats
        mesh, *_ = self._plan()
        over_vocab = NamedSharding(mesh, PartitionSpec(None, "mesh1"))
        # nothing given: the product's two output splits tie at no cost
        _, (cost, _), held = self._costs(None)
        assert cost == held == 0.0
        # the logits given sharded over the vocabulary: ``i0@1`` slices
        # them as they lie; ``j0@1`` wants the chain whole and pays the
        # gather, once
        graph, (cost, name), held = self._costs({0: over_vocab})
        assert (cost, name) == (0.0, "i0@1")
        logits = graph.nodes[next(iter(graph.relayouts))]
        nbytes = 512 * 1024 * 4
        assert held == graph.logical_mesh.all_gather_cost(nbytes, 1) > 0
        assert [st.out_spec for st in logits.strategies] == [((), (1,))]
        *_, (graph, choice) = self._plan({0: over_vocab},
                                         return_graph=True)
        assert given_stats(graph, choice) == {
            "given_in": 1, "given_sharded": 1, "given_reshard_bytes": 0}

    def test_a_second_reader_shares_the_gather(self):
        """Two products that want a given value whole pay one gather:
        they read it, as every reader does, one node behind the input."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
        from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
        from alpa_tpu.shard_parallel.ilp import solution_cost
        from alpa_tpu.shard_parallel.solver import (given_stats,
                                                    plan_auto_sharding)

        def two_readers(x, w1, w2):
            return x @ w1, jnp.tanh(x) @ w2

        avals = [jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in ((256, 512), (512, 8), (512, 8))]
        opt = AutoShardingOption(logical_mesh_shape=(1, 2),
                                 constrain_min_elements=0)
        mesh = LocalPhysicalDeviceMesh(jax.devices()[:2])
        jax_mesh, *_ = plan_auto_sharding(two_readers, avals, [""] * 3, [],
                                          mesh, opt)
        given = NamedSharding(jax_mesh, PartitionSpec(None, "mesh1"))
        _, in_sh, _, _, (graph, choice) = plan_auto_sharding(
            two_readers, avals, [""] * 3, [], mesh, opt,
            fixed_in={0: given}, return_graph=True)
        assert in_sh[0].spec == given.spec
        behind = [n for n in graph.nodes if n.label == "relayout0"]
        assert len(behind) == 1
        assert sorted(e.dst for e in graph.edges
                      if e.src == behind[0].idx) == sorted(
            n.idx for n in graph.nodes if n.label.startswith("dot"))
        # what is cheapest here: each product contracts over its slice
        # and all-reduces a [256, 8]; held whole, the value is gathered
        # once for both
        one_gather = graph.logical_mesh.all_gather_cost(256 * 512 * 4, 1)
        assert 0 < solution_cost(graph, choice) < one_gather
        from alpa_tpu.shard_parallel.ilp import solve_strategy_graph
        for dot in [n for n in graph.nodes if n.label.startswith("dot")]:
            self._only(graph, dot, "j0@1")
        choice = solve_strategy_graph(graph)
        assert solution_cost(graph, choice) == one_gather
        assert given_stats(graph, choice)["given_reshard_bytes"] == \
            256 * 512 * 4 // 2

    def test_a_product_reduce_scatters_onto_its_accumulator(self):
        """A weight gradient whose operands arrive sharded over the
        positions it contracts: the sum lands in a sharded accumulator by
        a reduce-scatter (half an all-reduce's bytes, and nothing whole on
        both chips), in the plan and in the compiled program."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
        from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
        from alpa_tpu.shard_parallel.ilp import solution_cost
        from alpa_tpu.shard_parallel.solver import (alias_stats,
                                                    plan_auto_sharding)

        def accumulate(x, dy, acc):
            return acc + x.T @ dy

        avals = [jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in ((512, 256), (512, 128), (256, 128))]
        opt = AutoShardingOption(logical_mesh_shape=(1, 2),
                                 constrain_min_elements=0)
        mesh = LocalPhysicalDeviceMesh(jax.devices()[:2])

        def plan(fixed_in):
            return plan_auto_sharding(
                accumulate, avals, [""] * 3, [], mesh, opt,
                alias_pairs=[(2, 0)], fixed_in=fixed_in, return_graph=True)

        jax_mesh, _, _, _, (graph, _) = plan(None)
        dot = next(n for n in graph.nodes if n.label.startswith("dot"))
        assert not any(">" in st.name for st in dot.strategies)
        rows = NamedSharding(jax_mesh, PartitionSpec("mesh1"))
        _, in_sh, cfn, _, (graph, choice) = plan({0: rows, 1: rows})
        dot = next(n for n in graph.nodes if n.label.startswith("dot"))
        chosen = dot.strategies[choice[dot.idx]]
        assert chosen.name.startswith("k0@1>")
        assert chosen.comm_kind == "reduce_scatter"
        assert not in_sh[2].is_fully_replicated
        lm = graph.logical_mesh
        nbytes = 256 * 128 * 4
        assert solution_cost(graph, choice) == \
            lm.reduce_scatter_cost(nbytes, 1) < lm.all_reduce_cost(nbytes, 1)
        assert alias_stats(graph, choice) == {
            "alias_pairs": 1, "alias_sharded": 1, "alias_reshard_bytes": 0}
        hlo = jax.jit(cfn, in_shardings=in_sh, out_shardings=in_sh[2],
                      donate_argnums=(2,)).lower(*avals).compile().as_text()
        assert "all-gather" not in hlo, hlo

    def test_every_reader_reads_a_given_value_behind_its_input(self):
        """The plan and the program agree on who reads the re-laid value:
        everybody.  An elementwise chain, a product and an output that
        follows the input all hang off ``relayout<i>``; only a donated
        pair's edge ends at the input's own node (the output leaves as the
        input arrives)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
        from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
        from alpa_tpu.shard_parallel.solver import (planned_out_specs,
                                                    plan_auto_sharding)

        def readers(x, w, side):
            return x * 2.0, (side + x) @ w, x + 1.0

        avals = [jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in ((256, 512), (512, 8), (256, 512))]
        opt = AutoShardingOption(logical_mesh_shape=(1, 2),
                                 constrain_min_elements=0)
        mesh = LocalPhysicalDeviceMesh(jax.devices()[:2])
        jax_mesh, *_ = plan_auto_sharding(readers, avals, [""] * 3, [],
                                          mesh, opt)
        given = NamedSharding(jax_mesh, PartitionSpec(None, "mesh1"))
        *_, (graph, choice) = plan_auto_sharding(
            readers, avals, [""] * 3, [], mesh, opt, alias_pairs=[(0, 2)],
            fixed_in={0: given}, return_graph=True)
        (inv, behind), = graph.relayouts.items()
        assert graph.nodes[behind].label == "relayout0"
        # out of the input's node: the one edge to the node behind it
        assert [e.dst for e in graph.edges if e.src == inv] == [behind]
        # the first output follows the value as the program reads it, the
        # donated one goes back to how it arrives
        assert graph.out_sources[0][0] == behind
        assert graph.out_sources[2][0] == behind
        assert [(e.src, e.dst) for e in graph.edges if e.dst == inv] == \
            [(behind, inv)]
        specs = planned_out_specs(graph, choice)
        assert specs[0] == \
            graph.nodes[behind].strategies[choice[behind]].out_spec
        assert specs[2] == graph.nodes[inv].strategies[0].out_spec

    def test_a_graph_with_nothing_given_is_the_parents(self):
        """Node for node and edge for edge (a digest taken on the parent
        commit), and under the cache key the parent made for it."""
        import hashlib
        from alpa_tpu.compile_cache import get_compile_cache
        from alpa_tpu.global_env import global_config
        *_, (graph, _) = self._plan(return_graph=True)
        assert graph.relayouts == {}
        h = hashlib.sha256()
        for n in graph.nodes:
            h.update(repr((n.idx, n.kind, n.label, n.invar_idx, [
                (st.name, st.out_spec, st.comm_cost, st.operand_specs,
                 st.mem_bytes, st.tie_bias) for st in n.strategies])
            ).encode())
        for e in graph.edges:
            h.update(repr((e.src, e.dst, e.cost.tolist())).encode())
        assert h.hexdigest() == GRAPH_DIGEST
        prev = global_config.compile_cache_enabled
        global_config.compile_cache_enabled = True
        try:
            cache = get_compile_cache()
            made = []
            make_key = cache.make_key
            cache.make_key = lambda ns, parts: made.append(
                make_key(ns, parts)) or made[-1]
            try:
                self._plan()
            finally:
                del cache.make_key
        finally:
            global_config.compile_cache_enabled = prev
        assert made == [ILP_KEY]


# of ``TestGivenInput``'s function with nothing given, on commit 0132b16
GRAPH_DIGEST = (
    "0f15f80b331e36af4909b30df68192b869ea0e6eb1cd11337f6e22de5aaf507e")
ILP_KEY = (
    "ilp-2cb5a519e46c0032414b3b728231153c98b9359ec59e1e83878cc20bfc67f5af")


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
