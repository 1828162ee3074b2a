"""The plain rules of the board, for `correct` in a rollout cell: which
cells are playable, which shapes exist, where a shape may be put, what a
move does to the board, the hand and the score, when a game is over,
and the net's input features. NumPy, no program code.

The game (the source's `trianglengin`, as `alphatriangle_tpu/env`
states it): a ROWS x COLS lattice of triangles, cell (r, c) pointing up
iff r + c is even; a row is playable between its `PLAYABLE_RANGE_PER_ROW`
bounds and dead outside; the shapes are all connected sets of
MIN..MAX_SHAPE_TRIANGLES triangles in fixed orientation, each parity of
the anchor a shape of its own, in sorted order within a size; action
`slot * ROWS * COLS + r * COLS + c` puts the slot's shape with its
origin at (r, c), legal iff every triangle lands inside the board, on a
cell of its own pointing, playable and empty.
"""

import functools

import numpy as np


def death_mask(env: dict) -> np.ndarray:
    death = np.ones((env["ROWS"], env["COLS"]), bool)
    for r, (lo, hi) in enumerate(env["PLAYABLE_RANGE_PER_ROW"]):
        death[r, lo:hi] = False
    return death


def _neighbours(r, c):
    side = [(r, c - 1), (r, c + 1)]
    return side + [(r + 1, c) if (r + c) % 2 == 0 else (r - 1, c)]


def _canonical(cells):
    """Shift to row 0 and column 0 or 1, by an even step so that every
    triangle keeps its pointing."""
    min_r = min(r for r, _ in cells)
    min_c = min(c for _, c in cells)
    dc = min_c if (min_r + min_c) % 2 == 0 else min_c - 1
    return tuple(sorted((r - min_r, c - dc) for r, c in cells))


def shapes(env: dict) -> list:
    """Every shape, as a tuple of (row, column) offsets."""
    level = {_canonical({(0, 0)}), _canonical({(0, 1)})}
    out = []
    for size in range(1, env["MAX_SHAPE_TRIANGLES"] + 1):
        if size >= env["MIN_SHAPE_TRIANGLES"]:
            out += sorted(level)
        grown = set()
        for shape in level:
            for r, c in shape:
                for cell in _neighbours(r, c):
                    if cell not in shape:
                        grown.add(_canonical(set(shape) | {cell}))
        level = grown
    return out


def unpack(words: np.ndarray, env: dict) -> np.ndarray:
    """(NW,) uint32 bitboard, cell r * COLS + c at bit (cell % 32) of
    word (cell // 32) -> (ROWS, COLS) bool."""
    cells = np.arange(env["ROWS"] * env["COLS"])
    bits = (np.asarray(words, np.uint32)[cells // 32] >> (cells % 32).astype(np.uint32)) & 1
    return bits.astype(bool).reshape(env["ROWS"], env["COLS"])


# --- moves: placing, clearing, rewards, the hand, the end of a game -------
#
# Placing a shape fills its triangles. Every line that is then full
# clears, all at once; a line is a maximal run of playable cells, of
# LINE_MIN_LENGTH or more, along one of the lattice's three directions:
# along a row; down to the right (from an up cell to its right
# neighbour, from a down cell to the cell below); down to the left (from
# an up cell to its left neighbour, from a down cell to the cell below).
# The move earns REWARD_PER_PLACED_TRIANGLE a triangle placed and
# REWARD_PER_CLEARED_TRIANGLE a cell cleared. The slot is then empty;
# when all are, a new hand is drawn. If no shape of the hand fits
# anywhere the game is over and the move earns PENALTY_GAME_OVER besides.


def line_masks(env: dict, death: np.ndarray) -> np.ndarray:
    """(L, ROWS * COLS) bool, one row a line."""
    rows, cols = death.shape

    def onward(kind, r, c):
        up = (r + c) % 2 == 0
        if kind == 0:
            return r, c + 1
        if up:
            return (r, c + 1) if kind == 1 else (r, c - 1)
        return r + 1, c

    out = []
    for kind in range(3):
        after = {}
        for r in range(rows):
            for c in range(cols):
                nr, nc = onward(kind, r, c)
                if (
                    not death[r, c]
                    and 0 <= nr < rows
                    and 0 <= nc < cols
                    and not death[nr, nc]
                ):
                    after[(r, c)] = (nr, nc)
        entered = set(after.values())
        for r in range(rows):
            for c in range(cols):
                if death[r, c] or (r, c) in entered:
                    continue
                run = [(r, c)]
                while run[-1] in after:
                    run.append(after[run[-1]])
                if len(run) >= env["LINE_MIN_LENGTH"]:
                    mask = np.zeros((rows, cols), bool)
                    for cell in run:
                        mask[cell] = True
                    out.append(mask.reshape(-1))
    return np.stack(out) if out else np.zeros((0, rows * cols), bool)


class Rules:
    """The tables of one board, and the moves on whole batches of
    boards: NumPy on flat (ROWS * COLS,) boards."""

    def __init__(self, env: dict):
        self.env = env
        self.rows, self.cols = env["ROWS"], env["COLS"]
        self.cells = self.rows * self.cols
        self.slots = env["NUM_SHAPE_SLOTS"]
        self.death = death_mask(env)
        self.dead = self.death.reshape(-1)
        self.bank = shapes(env)
        self.lines = line_masks(env, self.death).astype(np.float32)
        self.line_len = self.lines.sum(axis=1)
        n = len(self.bank)
        # foot[s, origin] is the shape's cells with its origin there;
        # fits[s, origin] says whether it lands on the board at all.
        self.foot = np.zeros((n + 1, self.cells, self.cells), bool)
        self.fits = np.zeros((n + 1, self.cells), bool)
        self.size = np.zeros(n + 1, np.float32)
        for s, shape in enumerate(self.bank):
            self.size[s] = len(shape)
            for origin in range(self.cells):
                r, c = divmod(origin, self.cols)
                at = [(r + dr, c + dc, (dr + dc) % 2) for dr, dc in shape]
                if all(
                    0 <= tr < self.rows
                    and 0 <= tc < self.cols
                    and (tr + tc) % 2 == par
                    and not self.death[tr, tc]
                    for tr, tc, par in at
                ):
                    self.fits[s, origin] = True
                    for tr, tc, _ in at:
                        self.foot[s, origin, tr * self.cols + tc] = True
        self._foot_flat = self.foot.reshape(-1, self.cells).astype(np.float32)
        self.shape_table = self._shape_table()

    def _shape_table(self) -> np.ndarray:
        """(S + 1, 7) per-shape features; the last row, all nought, is
        an empty slot's."""
        table = np.zeros((len(self.bank) + 1, 7), np.float32)
        for s, shape in enumerate(self.bank):
            rs = [r for r, _ in shape]
            cs = [c for _, c in shape]
            ups = sum((r + c) % 2 == 0 for r, c in shape)
            n = len(shape)
            table[s] = np.clip(
                [
                    n / 5.0,
                    ups / n,
                    (n - ups) / n,
                    (max(rs) - min(rs) + 1) / self.rows,
                    ((max(cs) - min(cs) + 1) * 0.75 + 0.25) / self.cols,
                    (min(rs) + max(rs)) / 2.0 / self.rows,
                    (min(cs) + max(cs)) / 2.0 / self.cols,
                ],
                0.0,
                1.0,
            )
        return table

    # every function below takes a batch of boards: occupied (N, cells)
    # bool, hand (N, slots) int (-1: an empty slot)

    def legal(self, occupied, hand) -> np.ndarray:
        """(N, slots * cells) bool."""
        if len(occupied) > 2048:  # in blocks: the product below is wide
            return np.concatenate(
                [
                    self.legal(occupied[at : at + 2048], hand[at : at + 2048])
                    for at in range(0, len(occupied), 2048)
                ]
            )
        blocked = (occupied | self.dead).astype(np.float32)
        # how many blocked cells each shape at each origin would cover
        hits = blocked @ self._foot_flat.T  # (N, (S + 1) * cells)
        hits = hits.reshape(len(occupied), len(self.fits), self.cells)
        mine = np.take_along_axis(hits, hand[:, :, None] % len(self.fits), axis=1)
        ok = (mine == 0) & self.fits[hand] & (hand >= 0)[:, :, None]
        return ok.reshape(len(occupied), self.slots * self.cells)

    def place(self, occupied, shape, origin):
        """The boards (M, cells) after shape `shape[i]` is put with its
        origin at `origin[i]` on board i and the full lines are cleared,
        before the hand is seen to: (child (M, cells) bool, gain (M,))."""
        placed = occupied | self.foot[shape, origin]
        full = (placed.astype(np.float32) @ self.lines.T) == self.line_len
        cleared = (full.astype(np.float32) @ self.lines) > 0
        gain = (
            self.size[shape] * self.env["REWARD_PER_PLACED_TRIANGLE"]
            + cleared.sum(axis=-1) * self.env["REWARD_PER_CLEARED_TRIANGLE"]
        )
        return placed & ~cleared, gain.astype(np.float32)

    def hand_after(self, hand, slot, drawn):
        """(M, slots): slot `slot[i]` of hand i emptied; `drawn[i]`
        where that leaves none."""
        left = hand.copy()
        left[np.arange(len(hand)), slot] = -1
        return np.where((left < 0).all(axis=1, keepdims=True), drawn, left)

    def features(self, occupied, hand, score, steps):
        """The net's inputs: grid (N, 1, ROWS, COLS) and the other
        features (N, 7 * slots + slots + 6), as `features/core.py`'s
        layout states them."""
        n = len(occupied)
        board = occupied.reshape(n, self.rows, self.cols) & ~self.death
        grid = np.where(self.death, -1.0, board.astype(np.float32))[:, None]
        row_no = np.arange(1, self.rows + 1)[None, :, None]
        height = np.where(board, row_no, 0).max(axis=1)  # (N, cols)
        under = (row_no - 1) < height[:, None, :]
        holes = (under & ~board & ~self.death).sum(axis=(1, 2))
        bump = np.abs(np.diff(height, axis=1)).sum(axis=1)
        playable = max(int((~self.death).sum()), 1)
        scalars = np.stack(
            [
                np.clip(score / 100.0, -5.0, 5.0),
                height.mean(axis=1) / self.rows,
                height.max(axis=1) / self.rows,
                holes / playable,
                bump / max(self.cols - 1, 1) / self.rows,
                np.clip(steps / 1000.0, 0.0, 1.0),
            ],
            axis=1,
        )
        other = np.concatenate(
            [self.shape_table[hand].reshape(n, 7 * self.slots), hand >= 0, scalars],
            axis=1,
        )
        return grid.astype(np.float32), other.astype(np.float32)


@functools.lru_cache(maxsize=None)
def _drawer(slots: int, n_shapes: int):
    import jax

    def one(key):
        key, sub = jax.random.split(key)
        return key, jax.random.randint(
            jax.random.split(sub)[0], (slots,), 0, n_shapes
        )

    return jax.jit(jax.vmap(one))


def draw_hands(keys: np.ndarray, slots: int, n_shapes: int):
    """What every move does to a game's key, and the hand it would draw
    were the hand empty: the key is split in two, the first half is the
    game's next key, the second is split again and its first half draws
    `slots` shape numbers. (N, 2) uint32 -> (next keys, (N, slots))."""
    keys, hands = _drawer(slots, n_shapes)(np.asarray(keys, np.uint32))
    return np.array(keys), np.array(hands)
