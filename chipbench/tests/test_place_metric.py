"""The reader of the mesh path's ``map1.place`` span, on numbers worked by
hand, and its silence where the program records no such span."""
import harness


def place():
    return harness.load_module("metrics", "map1.place_s.batch")


def test_place_seconds_per_family():
    # 3 families: 0.15 s in the span in all -> 0.05 s a family
    ctx = {"work": {"families": 3},
           "spans": {"map1.place": 0.15, "map1": 24.0}}
    assert abs(place().read(ctx) - 0.05) < 1e-12


def test_place_silent_without_families_or_span():
    assert place().read({"work": {"families": 0},
                         "spans": {"map1.place": 0.15}}) is None
    assert place().read({"work": {},
                         "spans": {"map1.place": 0.15}}) is None
    # a program without the span (the host path, or a mesh path that
    # does not split map(1)) reads as no value
    assert place().read({"work": {"families": 3},
                         "spans": {"map1": 24.0}}) is None
