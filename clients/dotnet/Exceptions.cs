// Typed client failure classes (the reference's dotnet exception
// classes — src/clients/dotnet/TigerBeetle/Exceptions.cs).  All
// extend IOException so earlier call sites keep compiling;
// catch the subtypes to distinguish retryable timeouts from fatal
// session states.
using System.IO;

namespace TigerBeetle;

public class ClientException : IOException
{
    public ClientException(string message) : base(message) { }
}

/// The per-request deadline elapsed before a reply arrived.  The
/// request may still commit server-side; retrying under the same
/// session observes the stored reply via at-most-once dedupe.
public sealed class RequestTimeoutException : ClientException
{
    public RequestTimeoutException(string message) : base(message) { }
}

/// The cluster evicted this session (too many live clients).  The
/// session is dead; build a NEW Client to continue.
public sealed class ClientEvictedException : ClientException
{
    public ClientEvictedException(string message) : base(message) { }
}

/// Request submitted after Dispose() — programming error.
public sealed class ClientClosedException : ClientException
{
    public ClientClosedException(string message) : base(message) { }
}

/// The peer sent a malformed frame (bad size word or checksum).
public sealed class InvalidFrameException : ClientException
{
    public InvalidFrameException(string message) : base(message) { }
}
