"""Plain references the benchmark's `correct` compares against.

Nothing here imports the program under test (`repro`): the references are
straightforward numpy restatements of the semantics the program documents,
fed only the problem's sizes and the program's answers.
"""
