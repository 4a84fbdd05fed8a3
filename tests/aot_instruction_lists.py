"""Write the v5e-compiled paged serve programs' normalised instruction
lists, for comparing two trees::

    JAX_PLATFORMS=cpu python tests/aot_instruction_lists.py <tree> <out>

``<tree>`` is the checkout whose ``mxtpu`` is compiled (this one, or a
``git archive`` of another commit); the shapes are
``tests/test_tpu_aot_scopes.py``'s. One file a program in ``<out>``:
``decode_slots_paged``, ``decode_slots_spec``, ``prefill_slot_paged``,
``copy_page``, ``sambay.decode_slots_paged``,
``latent_moe.decode_slots_paged`` and ``retention.decode_slots_paged``,
a line an instruction of the
optimised module — computation, opcode, result type with its layout, ``op_name``
— with XLA's instruction numbering taken out. Two trees that give
``diff -r`` nothing hand the chip the same programs. With a third
argument, a scope's name (``sampler``), only the instructions that the
model's OTHER scopes name are listed (an ``op_name`` that starts with
``jit(`` and does not pass through that scope; what XLA makes without a
name, a ``cumsum``'s windows or a fusion's parameters, cannot be told
apart and is left out), so that two trees which differ under that scope
alone give ``diff -r`` nothing but the fusions on its border. Not a
test: one process may hold libtpu, so run it on its own, once a tree."""
import os
import re
import sys


def _normal(text, without=None):
    """HLO text -> its instructions, one line each, without the numbers
    XLA appends to names (``fusion.195``) or what only they change; with
    ``without``, only those named by another scope of the model."""
    number = re.compile(r"[._]\d+\b")
    computation = re.compile(r"\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
    instruction = re.compile(
        r"\s*(ROOT\s+)?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")
    op_name = re.compile(r'op_name="([^"]*)"')
    under = re.compile(rf"[/(](?:{without})[/)]") if without else None
    out, cur = [], ""
    for line in text.splitlines():
        m = computation.match(line)
        if m:
            cur = number.sub("", m.group(1))
            continue
        m = instruction.match(line)
        if m:
            scope = op_name.search(line)
            if under and not (scope and scope.group(1).startswith("jit(")
                              and not under.search(scope.group(1))):
                continue
            out.append(" ".join((
                cur, "ROOT" if m.group(1) else "-", m.group(3),
                m.group(2),
                scope.group(1) if scope else "")))
    return out


def main(tree, out_dir, without=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:0] = [os.path.abspath(tree), here]
    import test_tpu_aot_scopes as aot
    one_chip = aot.one_chip.__wrapped__()
    texts = {name: exe.as_text() for name, exe
             in aot.compiled.__wrapped__(one_chip).items()}
    texts["sambay.decode_slots_paged"] = \
        aot.sambay_decode.__wrapped__(one_chip).as_text()
    texts["latent_moe.decode_slots_paged"] = \
        aot.latent_moe_decode.__wrapped__(one_chip)[2].as_text()
    texts["retention.decode_slots_paged"] = \
        aot.retention_decode.__wrapped__(one_chip)[2].as_text()
    os.makedirs(out_dir, exist_ok=True)
    for name, text in texts.items():
        lines = _normal(text, without)
        with open(os.path.join(out_dir, name + ".txt"), "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"{name}: {len(lines)} instructions")


if __name__ == "__main__":
    main(*sys.argv[1:4])
