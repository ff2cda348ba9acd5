// Typed client failure classes (the reference's per-condition
// exception classes — src/clients/java/src/main/java/com/tigerbeetle/
// RequestException.java and friends).  All extend IOException so
// earlier call sites keep compiling; catch the subtypes to
// distinguish retryable timeouts from fatal session states.
package com.tigerbeetle;

import java.io.IOException;

public class ClientException extends IOException {
    public ClientException(String message) {
        super(message);
    }
}
