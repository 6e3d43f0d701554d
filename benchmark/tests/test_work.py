import importlib.util
import pathlib

spec = importlib.util.spec_from_file_location(
    "bench_work", pathlib.Path(__file__).resolve().parents[1] / "work.py")
work = importlib.util.module_from_spec(spec)
spec.loader.exec_module(work)


def test_bfs_bytes_counts_four_terms():
    # 10 states of 3 words, 40 successors: rows read 120 + appended 120,
    # probes 320, inserts 80.
    assert work.bfs_bytes(10, 40, 3) == 120 + 120 + 320 + 80


def test_bfs_bytes_paxos3_at_the_golden():
    # 1,194,428 unique states of 42 words: 2 x 168 B rows + an 8 B insert
    # each, and 8 B per generated successor.
    u, g = 1_194_428, 10_000_000
    assert work.bfs_bytes(u, g, 42) == u * (2 * 168 + 8) + g * 8
