"""The program's side of the ``boxgame`` family: upstream ex_game's BoxGame
and its one-byte button-mask input."""

from __future__ import annotations

import numpy as np

from ggrs_tpu.games import BoxGame, boxgame_config


def make_game(config: dict):
    return BoxGame(int(config["players"]))


def session_config():
    return boxgame_config()


def example_inputs(config: dict) -> np.ndarray:
    return np.zeros((int(config["players"]),), np.uint8)


def inputs_to_array(pairs) -> np.ndarray:
    return np.asarray([p[0] for p in pairs], np.uint8)


def raw_inputs_to_array(blobs: np.ndarray, statuses: np.ndarray) -> np.ndarray:
    # the config encodes a mask as one little-endian uint: byte 0 is the value
    return blobs[:, :, 0]
