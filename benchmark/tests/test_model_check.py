"""The model check under the slim followers: what a follow reads goes
through files and comes back a tensor at a time, and every number it
prints on the token fixtures is the one the parent printed for the same
seed, when the followers held four whole copies of each arm's state on the
host; arms resident one at a time print the same again."""

import pytest

from benchmark import model_check, rehearse, run

#: ``model_check``'s numbers at the parent (commit 5f98036, XLA:CPU on one
#: and on four virtual devices, ``seconds`` 0.2), seeds 0, 1, 2147483659:
#: each arm's steps and the ``max`` of each number compared, the program's
#: losses, and ``norm_gap_max`` where the parent printed one.
PARENT = {
 "tiny_lm.resident": {
  0: {
   "dgc": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "conserved_rel_err": 1.0173116024195875e-05
   },
   "dense": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "grad_rel_err": 0.0,
    "update_norm_gap": 1.2064717177818486e-07
   },
   "losses": {
    "dgc": [
     6.234123229980469,
     6.2290802001953125,
     6.22398567199707
    ],
    "dense": [
     6.234123229980469,
     6.227328300476074,
     6.21441125869751
    ]
   },
   "norm_gap_max": {
    "dgc.conserved_rel_err": 3.9021295870148636e-07,
    "dense.grad_rel_err": 0.0
   }
  },
  1: {
   "dgc": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "conserved_rel_err": 1.3511181513171889e-05
   },
   "dense": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "grad_rel_err": 0.0,
    "update_norm_gap": 2.430607089896639e-07
   },
   "losses": {
    "dgc": [
     6.255537986755371,
     6.251348495483398,
     6.246855735778809
    ],
    "dense": [
     6.255537986755371,
     6.249508857727051,
     6.238057613372803
    ]
   },
   "norm_gap_max": {
    "dgc.conserved_rel_err": 2.905953468643332e-07,
    "dense.grad_rel_err": 0.0
   }
  },
  2147483659: {
   "dgc": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "conserved_rel_err": 1.207080732575554e-05
   },
   "dense": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "grad_rel_err": 0.0,
    "update_norm_gap": 6.088267505874735e-08
   },
   "losses": {
    "dgc": [
     6.280308723449707,
     6.276399612426758,
     6.271605491638184
    ],
    "dense": [
     6.280308723449707,
     6.2740068435668945,
     6.262047290802002
    ]
   },
   "norm_gap_max": {
    "dgc.conserved_rel_err": 3.8351038476704984e-07,
    "dense.grad_rel_err": 0.0
   }
  }
 },
 "tiny_lm.scan": {
  0: {
   "dgc": {
    "steps": 9,
    "loss_rel_err": 0.0
   },
   "dense": {
    "steps": 9,
    "loss_rel_err": 0.0,
    "update_norm_gap": 8.207378941694558e-08
   },
   "losses": {
    "dgc": [
     6.256133079528809,
     6.247332572937012,
     6.225039482116699
    ],
    "dense": [
     6.256133079528809,
     6.264249801635742,
     6.243891716003418,
     6.232235431671143,
     6.248222351074219,
     6.2000908851623535,
     6.179965019226074,
     6.2205328941345215,
     6.133347034454346
    ]
   },
   "norm_gap_max": {}
  },
  1: {
   "dgc": {
    "steps": 9,
    "loss_rel_err": 0.0
   },
   "dense": {
    "steps": 9,
    "loss_rel_err": 0.0,
    "update_norm_gap": 5.1163942244800776e-08
   },
   "losses": {
    "dgc": [
     6.267085552215576,
     6.258498191833496,
     6.234722137451172
    ],
    "dense": [
     6.267085552215576,
     6.265712738037109,
     6.254763603210449,
     6.243032932281494,
     6.247849464416504,
     6.210686683654785,
     6.190439224243164,
     6.217015266418457,
     6.143549919128418
    ]
   },
   "norm_gap_max": {}
  },
  2147483659: {
   "dgc": {
    "steps": 9,
    "loss_rel_err": 0.0
   },
   "dense": {
    "steps": 9,
    "loss_rel_err": 0.0,
    "update_norm_gap": 7.357312368849205e-08
   },
   "losses": {
    "dgc": [
     6.285160064697266,
     6.276437759399414,
     6.253898620605469
    ],
    "dense": [
     6.285160064697266,
     6.230731010437012,
     6.272978782653809,
     6.261385440826416,
     6.2131242752075195,
     6.229428768157959,
     6.209434986114502,
     6.182712078094482,
     6.163153648376465
    ]
   },
   "norm_gap_max": {}
  }
 },
 "tiny_lm.resident.x4": {
  0: {
   "dgc": {
    "steps": 3,
    "loss_rel_err": 7.608611213129906e-08,
    "conserved_rel_err": 3.5776752288702535e-05
   },
   "dense": {
    "steps": 3,
    "loss_rel_err": 7.608611213129906e-08,
    "grad_rel_err": 3.053904542031175e-07,
    "update_norm_gap": 1.8701232065219012e-07
   },
   "losses": {
    "dgc": [
     6.267072677612305,
     6.265509128570557,
     6.263920307159424
    ],
    "dense": [
     6.267072677612305,
     6.265063285827637,
     6.261247634887695
    ]
   },
   "norm_gap_max": {
    "dgc.conserved_rel_err": 1.4753958698791885e-06,
    "dense.grad_rel_err": 2.2780508035655828e-08
   }
  },
  1: {
   "dgc": {
    "steps": 3,
    "loss_rel_err": 1.5198494619504928e-07,
    "conserved_rel_err": 4.309978892226826e-05
   },
   "dense": {
    "steps": 3,
    "loss_rel_err": 7.603930136914845e-08,
    "grad_rel_err": 3.34269377922398e-07,
    "update_norm_gap": 1.6985978654926747e-07
   },
   "losses": {
    "dgc": [
     6.276063919067383,
     6.27479362487793,
     6.273430347442627
    ],
    "dense": [
     6.276063919067383,
     6.274292469024658,
     6.270930767059326
    ]
   },
   "norm_gap_max": {
    "dgc.conserved_rel_err": 2.5558283082252836e-06,
    "dense.grad_rel_err": 1.7950415829123526e-08
   }
  },
  2147483659: {
   "dgc": {
    "steps": 3,
    "loss_rel_err": 0.0,
    "conserved_rel_err": 3.949110058698484e-05
   },
   "dense": {
    "steps": 3,
    "loss_rel_err": 7.614757247250025e-08,
    "grad_rel_err": 3.1659995651605574e-07,
    "update_norm_gap": 2.7059628950738585e-07
   },
   "losses": {
    "dgc": [
     6.26716423034668,
     6.265932083129883,
     6.2645416259765625
    ],
    "dense": [
     6.26716423034668,
     6.265386581420898,
     6.2620134353637695
    ]
   },
   "norm_gap_max": {
    "dgc.conserved_rel_err": 1.304284404766748e-06,
    "dense.grad_rel_err": 9.785479589237163e-09
   }
  }
 }
}

#: the cells whose traffic files differ from a pinned one in ``residency``
#: alone: one arm at a time, the same weights, batches and dispatches
SAME_AS = {"tiny_lm.one": "tiny_lm.resident",
           "tiny_lm.one.x4": "tiny_lm.resident.x4"}


def _maxima(model, arm):
    return {key: value["max"] if isinstance(value, dict) else value
            for key, value in model["arms"][arm].items()}


@pytest.mark.parametrize("seed", [0, 1, 2147483659])
@pytest.mark.parametrize("name", sorted(PARENT) + sorted(SAME_AS))
def test_the_model_check_prints_the_parents_numbers(name, seed):
    import jax
    m = run.measure(rehearse.fixture_cell(name), seed=seed, seconds=0.2,
                    trace=False, devices=jax.devices("cpu"))
    want = PARENT[SAME_AS.get(name, name)][seed]
    model = m["model_check"]
    assert model["ok"] and run.is_correct(m)
    for arm in ("dgc", "dense"):
        assert _maxima(model, arm) == want[arm]
        assert model["arms"][arm]["loss_rel_err"]["program"] \
            == want["losses"][arm]
    assert {f"{arm}.{key}": value["norm_gap_max"]
            for arm, numbers in model["arms"].items()
            for key, value in numbers.items()
            if isinstance(value, dict) and "norm_gap_max" in value} \
        == want["norm_gap_max"]


@pytest.mark.parametrize("name", ["tiny_lm.resident", "tiny_lm.resident.x4"])
def test_a_tensor_followed_in_pieces_reads_as_it_does_whole(
        name, monkeypatch):
    """The fixture's tensors are smaller than a piece; cut into pieces of
    777 elements their sums of squares are taken in another order, and
    every number stays the parent's to float64's rounding."""
    import jax
    monkeypatch.setattr(model_check, "PIECE", 777)
    m = run.measure(rehearse.fixture_cell(name), seed=1, seconds=0.2,
                    trace=False, devices=jax.devices("cpu"))
    want = PARENT[name][1]
    for arm in ("dgc", "dense"):
        assert _maxima(m["model_check"], arm) == pytest.approx(
            want[arm], rel=1e-9, abs=1e-18)
