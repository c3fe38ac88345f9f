"""Plain reference of a cell: flow environment, policies and learner, in
straightforward jax.numpy, independent of the program under test."""
