"""Lazy device checksums.

Round-3 perf redesign contract: the executor's save path attaches
``DeviceChecksum`` handles (no device→host read until the value is actually
consumed)."""

from ggrs_tpu.core.sync_layer import GameStateCell
from ggrs_tpu.games import BoxGame
from ggrs_tpu.ops import pytree_checksum
from ggrs_tpu.ops.checksum import DeviceChecksum, checksum_device


class TestDeviceChecksum:
    def test_materializes_to_pytree_checksum(self):
        state = BoxGame(2).init_state()
        lazy = DeviceChecksum(checksum_device(state))
        assert lazy.materialize() == pytree_checksum(state)
        assert int(lazy) == pytree_checksum(state)  # cached second read

    def test_cell_accepts_lazy_and_property_materializes(self):
        state = BoxGame(2).init_state()
        cell = GameStateCell()
        cell.save(7, state, DeviceChecksum(checksum_device(state)))
        got = cell.checksum
        assert isinstance(got, int)
        assert got == pytree_checksum(state)
        assert 0 <= got < (1 << 128)

    def test_cell_still_validates_int_range(self):
        cell = GameStateCell()
        try:
            cell.save(1, None, 1 << 128)
        except ValueError:
            pass
        else:  # pragma: no cover
            raise AssertionError("expected ValueError for out-of-range int")

    def test_equality_against_plain_int(self):
        state = BoxGame(2).init_state()
        lazy = DeviceChecksum(checksum_device(state))
        assert lazy == pytree_checksum(state)
