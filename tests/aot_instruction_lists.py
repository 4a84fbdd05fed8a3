"""Write the v5e-compiled paged serve programs' normalised instruction
lists, for comparing two trees::

    JAX_PLATFORMS=cpu python tests/aot_instruction_lists.py <tree> <out>

``<tree>`` is the checkout whose ``mxtpu`` is compiled (this one, or a
``git archive`` of another commit); the shapes are
``tests/test_tpu_aot_scopes.py``'s. One file a program in ``<out>``:
``decode_slots_paged``, ``decode_slots_spec``, ``prefill_slot_paged``,
``copy_page``, ``sambay.decode_slots_paged`` and
``latent_moe.decode_slots_paged``, a line an instruction of the
optimised module — computation, opcode, result type with its layout, ``op_name``
— with XLA's instruction numbering taken out. Two trees that give
``diff -r`` nothing hand the chip the same programs. Not a test: one
process may hold libtpu, so run it on its own, once a tree."""
import os
import re
import sys


def _normal(text):
    """HLO text -> its instructions, one line each, without the numbers
    XLA appends to names (``fusion.195``) or what only they change."""
    number = re.compile(r"[._]\d+\b")
    computation = re.compile(r"\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{$")
    instruction = re.compile(
        r"\s*(ROOT\s+)?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")
    op_name = re.compile(r'op_name="([^"]*)"')
    out, cur = [], ""
    for line in text.splitlines():
        m = computation.match(line)
        if m:
            cur = number.sub("", m.group(1))
            continue
        m = instruction.match(line)
        if m:
            scope = op_name.search(line)
            out.append(" ".join((
                cur, "ROOT" if m.group(1) else "-", m.group(3),
                m.group(2),
                scope.group(1) if scope else "")))
    return out


def main(tree, out_dir):
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
    os.makedirs(out_dir, exist_ok=True)
    for name, text in texts.items():
        lines = _normal(text)
        with open(os.path.join(out_dir, name + ".txt"), "w") as f:
            f.write("\n".join(lines) + "\n")
        print(f"{name}: {len(lines)} instructions")


if __name__ == "__main__":
    main(*sys.argv[1:3])
